"""KV manager: bytes of cache held a live token, where the tree keeps a store
per slot beside the block pool: ``StepRecord.kv_bytes_held`` (the used blocks
and the seated slots' ring rows, as the arrays store them, lane padding
included) over ``StepRecord.kv_live_tokens`` (the seated requests' tokens at
the end of the step), median over the steps with a token-generation dispatch.
bytes. A cache that held every layer by block would read the sum of all
layers' rows a token; the window layers cost a constant a slot instead.
Nothing to read where the program records neither (every tree that is one
pool, and the parent of PR 35)."""

from benchmark.records import median


def read(run):
    per_token = [
        r.kv_bytes_held / r.kv_live_tokens
        for r in run.steps
        if r.decode is not None
        and getattr(r, "kv_bytes_held", None) is not None
        and getattr(r, "kv_live_tokens", None)
    ]
    return median(per_token) if per_token else None
