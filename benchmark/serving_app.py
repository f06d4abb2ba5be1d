"""A configuration file -> the loaded serving app and its engine.

The app is the program's own application class for the family (the family
module's ``APPLICATION_CLS`` where it has one, else ``TpuModelForCausalLM``)
with the serving stack of ``chip_smoke.paged_tpu_config`` (paged KV, flash
prefill + paged decode kernels, greedy sampling on the device). Two things are
the benchmark's: the weights, drawn on the device from the seed in one jitted
call with the app's own shardings, and the empty cache: whatever tree the app
declares (``_cache_struct()``: a k/v pool, a latent pool, a window ring, state
beside the pool), made sharded instead of whole on the first device. Neither
reads a checkpoint.
"""

from __future__ import annotations

from typing import List, Sequence

#: the benchmark's own keys of a configuration file; every other top-level
#: key is the published ``config.json`` and goes to the program's config class
#: as it is (a family's expert, latent, window or state keys with it)
BENCHMARK_KEYS = ("name", "source", "deployment", "reduced", "assumed", "benchmark")

WEIGHT_STD = 0.02
BIAS_STD = 0.5  # q/k/v biases of a trained qwen2 are O(1); a dropped one must show


def prompt_buckets(max_prompt: int, floor: int = 256) -> List[int]:
    """Powers of two from ``floor`` up to the first that holds ``max_prompt``:
    a cell compiles only what its own traffic can reach."""
    out = [floor]
    while out[-1] < max_prompt:
        out.append(out[-1] * 2)
    return out


def tpu_config_of(config: dict, buckets: Sequence[int], flight_records: int):
    from nxdi_tpu.config import OnDeviceSamplingConfig, TpuConfig

    b = config["benchmark"]
    return TpuConfig(
        tp_degree=b["tp"],
        batch_size=b["slots"],
        ctx_batch_size=b["ctx_batch_size"],
        tkg_batch_size=b["slots"],
        seq_len=b["seq_len"],
        max_context_length=max(buckets),
        context_encoding_buckets=list(buckets),
        dtype="bfloat16",
        on_device_sampling_config=OnDeviceSamplingConfig(),
        is_block_kv_layout=True,
        pa_block_size=b["pa_block_size"],
        pa_num_blocks=b["pa_num_blocks"],
        attn_kernel_enabled=True,
        attn_block_tkg_kernel_enabled=True,
        telemetry={
            "detail": "basic",
            "flight_records": flight_records,
            "trace": False,
        },
        **b.get("tpu_config", {}),  # what else a family's serving stack needs
    )


def leaf_kind(path) -> str:
    """``norm``, ``bias`` or ``weight``, from a parameter leaf's tree path."""
    keys = [getattr(p, "key", None) for p in path]
    if any(isinstance(k, str) and k.endswith("norm") for k in keys):
        return "norm"
    return "bias" if keys[-1] == "b" else "weight"


def seeded_params(struct, shardings, seed: int):
    """The whole parameter tree from ``seed`` in ONE jitted call, each leaf in
    the dtype and sharding it is served in. Norm weights are 1, biases normal
    x ``BIAS_STD``, every other leaf normal x ``WEIGHT_STD``."""
    import jax
    import jax.numpy as jnp

    paths, treedef = jax.tree_util.tree_flatten_with_path(struct)

    kinds = [leaf_kind(path) for path, _ in paths]
    shapes = [s for _, s in paths]

    def make(key):
        leaves = []
        for i, (k, s) in enumerate(zip(kinds, shapes)):
            if k == "norm":
                leaves.append(jnp.ones(s.shape, s.dtype))
                continue
            std = BIAS_STD if k == "bias" else WEIGHT_STD
            draw = jax.random.normal(jax.random.fold_in(key, i), s.shape, jnp.float32)
            leaves.append((draw * std).astype(s.dtype))
        return jax.tree_util.tree_unflatten(treedef, leaves)

    return jax.jit(make, out_shardings=shardings)(jax.random.key(seed % (2**63)))


def empty_cache(struct, specs, mesh):
    """Zeros for every leaf of ``struct`` (a tree of ``ShapeDtypeStruct``: the
    app's ``_cache_struct()``), each in its own shape and dtype, sharded as
    ``specs`` (a tree of ``PartitionSpec`` with the same keys) says on
    ``mesh``, in ONE jitted call: no leaf is ever whole on one device."""
    import jax
    import jax.numpy as jnp

    from nxdi_tpu.parallel.layers import sharding_tree

    def zeros():
        return jax.tree_util.tree_map(lambda s: jnp.zeros(s.shape, s.dtype), struct)

    return jax.jit(zeros, out_shardings=sharding_tree(specs, mesh))()


def application_class(family):
    """The program's convention (``cli/inference_demo.py``): a family with an
    application class of its own names it ``APPLICATION_CLS`` in its module."""
    from nxdi_tpu.runtime.application import TpuModelForCausalLM

    return getattr(family, "APPLICATION_CLS", TpuModelForCausalLM)


def build_app(config: dict, buckets: Sequence[int], seed: int, flight_records: int = 1 << 17):
    """The un-loaded app of ``config`` (call ``.load()`` on it)."""
    from nxdi_tpu.models.registry import get_family
    from nxdi_tpu.parallel.layers import sharding_tree

    published = {k: v for k, v in config.items() if k not in BENCHMARK_KEYS}
    family, cfg_cls = get_family(config["model_type"])
    inference_config = cfg_cls(
        tpu_config_of(config, buckets, flight_records), load_config=lambda: dict(published)
    )

    class SeededApp(application_class(family)):
        def build_params(self):
            return seeded_params(
                self.build_params_struct(),
                sharding_tree(self.param_specs(), self.mesh),
                seed,
            )

        def init_cache_host(self):
            # the program's own makes the whole pool on the first device and
            # shards it afterwards, which a four-chip pool does not survive
            return empty_cache(self._cache_struct(), self.cache_partition_specs(), self.mesh)

    return SeededApp(f"<seeded:{config['name']}>", inference_config, model_family=family)


def program_strategies(app) -> dict:
    """``{program label: [attention strategies]}`` as each program recorded
    them at lowering (what ``chip_smoke.require_strategy`` reads)."""
    return {
        prog.label: list(prog.attention_strategies)
        for wrapper in app.models.values()
        for prog in wrapper._programs.values()
    }


def strategy_faults(app, expected: dict) -> List[str]:
    """Programs that did not take the strategy the configuration expects: a
    silent XLA fall-back must make the run incorrect, not merely slower."""
    faults = []
    strategies = program_strategies(app)
    for prefix, want in expected.items():
        hits = {k: v for k, v in strategies.items() if k.startswith(prefix)}
        if not hits:
            faults.append(f"no program {prefix}* among {sorted(strategies)}")
        for label, got in hits.items():
            if want not in got:
                faults.append(f"{label}: expected {want}, recorded {got}")
    return faults


def program_module_names(app) -> dict:
    """``{XLA module name: program label}`` for the app's step programs. The
    paged programs compile under a name drawn per process (they are kept out
    of the persistent cache), so the trace reducer is told the names."""
    out = {}
    for wrapper in app.models.values():
        for prog in wrapper._programs.values():
            name = getattr(prog.jitted, "__name__", None) or "?"
            out[f"jit_{name}"] = prog.label
    return out
