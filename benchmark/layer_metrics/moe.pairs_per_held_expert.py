"""Model step programs, expert layer: (row, expert) pairs that fell on a held
expert, per held expert per routed layer, mean over the decode steps:
``StepRecord.moe_held_pairs`` (counted inside the token-generation program and
returned beside the tokens; batch-padding rows are in it) over
``StepRecord.moe_routed_layers`` x the configuration's held experts. 4.0 where
a cut keeps the deployment's load on an expert (slots x top k / all experts).
count. Nothing to read where the program returns no such count (a model
without routed experts, or one that holds them all)."""


def read(run):
    held = run.config.get("n_routed_experts")
    per_layer = [
        r.moe_held_pairs / r.moe_routed_layers
        for r in run.steps
        if r.decode is not None and getattr(r, "moe_held_pairs", None) is not None
        and getattr(r, "moe_routed_layers", None)
    ]
    if not per_layer or not held:
        return None
    return sum(per_layer) / len(per_layer) / held
