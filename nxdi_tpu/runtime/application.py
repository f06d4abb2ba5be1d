"""Application lifecycle: compile -> save -> load -> forward.

The analog of the reference's ``NeuronApplicationBase``/``NeuronBaseForCausalLM``
(models/application_base.py:292 compile, :317 load, :348 warmup;
models/model_base.py:3078 CausalLM submodel construction and :3367 dispatch).

Artifact model: the reference serializes traced NEFFs into
``--compiled-model-path``. Here the artifact directory holds
  - ``tpu_config.json``   — the InferenceConfig round trip (config.py),
  - ``weights/``          — optional presharded safetensors.
The compiled programs themselves live in JAX's persistent compilation cache,
written by AOT ``lower().compile()`` of every bucket program so a later
``load()`` never recompiles; its directory is decided by
:func:`enable_persistent_cache`, not by the artifact path.
"""

from __future__ import annotations

import logging
import os
import time
from typing import Any, Dict, Optional

import jax
import numpy as np

from nxdi_tpu import checkpoint as ckpt
from nxdi_tpu.config import InferenceConfig
from nxdi_tpu.kvcache.kv_cache import (
    BlockKVCacheSpec,
    block_kv_cache_partition_spec,
    init_block_kv_cache,
    init_kv_cache,
    kv_cache_partition_spec,
)
from nxdi_tpu.parallel.layers import shard_pytree, sharding_tree
from nxdi_tpu.parallel.mesh import mesh_from_config
from nxdi_tpu.runtime import autobucketing
from nxdi_tpu.runtime.model_wrapper import (
    TAG_CONTEXT_ENCODING,
    TAG_DEVICE_LOOP,
    TAG_MIXED,
    TAG_TOKEN_GENERATION,
    TAG_TOKEN_GENERATION_MULTISTEP,
    DeviceLoopTKGWrapper,
    MixedModelWrapper,
    ModelWrapper,
    MultiStepTKGWrapper,
)

TAG_PREFIX_PREFILL = "prefix_prefill_model"

logger = logging.getLogger("nxdi_tpu")


def maybe_quantize_params(params, tc):
    """Apply weight quantization per the TpuConfig (no-op unless quantized).
    Shared by every application subclass, including ones that override
    build_params (fused speculation's draft/target sub-pytrees)."""
    if not tc.quantized:
        return params
    from nxdi_tpu.ops import quantization as quant_ops

    return quant_ops.quantize_params(
        params,
        quant_dtype=tc.quantization_dtype,
        scheme=tc.quantization_type,
        modules_to_not_convert=tc.modules_to_not_convert,
        static_input_scales=tc.activation_quantization_type == "static",
    )


def maybe_quantize_specs(specs, tc):
    if not tc.quantized:
        return specs
    from nxdi_tpu.ops import quantization as quant_ops

    return quant_ops.quantize_param_specs(
        specs, scheme=tc.quantization_type,
        modules_to_not_convert=tc.modules_to_not_convert,
        quant_dtype=tc.quantization_dtype,
        static_input_scales=tc.activation_quantization_type == "static",
    )


def maybe_quantize_struct(struct, tc):
    if not tc.quantized:
        return struct
    from nxdi_tpu.ops import quantization as quant_ops

    return quant_ops.quantize_shape_struct(
        struct,
        quant_dtype=tc.quantization_dtype,
        scheme=tc.quantization_type,
        modules_to_not_convert=tc.modules_to_not_convert,
        static_input_scales=tc.activation_quantization_type == "static",
    )


#: the compile cache's place when the environment names none: one fixed path
#: inside the checkout (listed in .gitignore). The path is part of how an
#: entry is found again, so it never holds a temp name, a pid or a time.
DEFAULT_COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)


def enable_persistent_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    THE one place that decides the directory: ``JAX_COMPILATION_CACHE_DIR``
    when the environment sets it (JAX reads that variable itself, so no
    directory is set in code), else :data:`DEFAULT_COMPILE_CACHE_DIR`. Call
    before the first compilation of the process — JAX decides once whether
    the cache is in use."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = DEFAULT_COMPILE_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    os.makedirs(path, exist_ok=True)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    # An entry's key must not depend on the Python call path that lowered the
    # program. JAX strips locations from the module it hashes, but a Mosaic
    # kernel rides in it as serialized bytes WITH its locations, and by
    # default those hold the traceback of the pallas_call: the same TKG
    # program lowered from compile() and from load() got two keys on the
    # v5e, so a load never found what compile had written. One frame
    # (file:line of the op) is the same from every caller.
    jax.config.update("jax_include_full_tracebacks_in_locations", False)
    return path


class ApplicationBase:
    """Owns the submodel ModelWrappers + device state (params, KV cache)."""

    _model_cls = None  # model-family module; set by subclasses/registry

    def __init__(self, model_path: str, config: InferenceConfig, model_family=None):
        self.model_path = model_path
        self.config = config
        self.tpu_config = config.tpu_config
        self.family = model_family or self._model_cls
        if self.family is None:
            raise ValueError("No model family bound to this application")
        self.models: Dict[str, ModelWrapper] = {}
        self.mesh = None
        self.params = None
        self.kv_cache = None
        self.is_loaded = False
        self.retrace_guard = None  # created in _build_wrappers per TpuConfig
        # the params' formats and what load moved (runtime/param_layout.py)
        self.param_layout = None
        # serving telemetry (nxdi_tpu/telemetry): always-on registry + spans,
        # per TpuConfig(telemetry=...); the wrappers, generation adapter,
        # block manager, and retrace guard all record into it
        from nxdi_tpu.telemetry import Telemetry

        self.telemetry = Telemetry.from_config(self.tpu_config)

    # -- submodel construction: subclasses populate self.models --
    def enable_models(self) -> None:
        raise NotImplementedError

    # ------------------------------------------------------------------
    def get_state_dict(self) -> Dict[str, np.ndarray]:
        """HF checkpoint -> flat numpy dict, with reference-compatible prefix
        normalization (application_base.py:691 get_state_dict)."""
        sd = ckpt.load_state_dict(self.model_path)
        return sd

    def build_params_with_extras(self, base_build, extra_converter) -> Any:
        """``base_build()`` (the subclass's ``super().build_params``) + extra
        sub-pytrees from the SAME checkpoint read: memoizes get_state_dict so
        the text conversion and ``extra_converter(sd, config) -> dict`` share
        one multi-GB safetensors load (multimodal apps: vision towers,
        projectors)."""
        real_get = self.get_state_dict
        memo = {}

        def cached():
            if "sd" not in memo:
                memo["sd"] = real_get()
            return memo["sd"]

        self.get_state_dict = cached
        try:
            params = base_build()
            params.update(extra_converter(cached(), self.config))
        finally:
            self.get_state_dict = real_get
        return params

    def build_params(self) -> Any:
        tc = self.tpu_config
        if tc.quantized and tc.quantized_checkpoints_path:
            # pre-quantized artifact (reference: quantized_checkpoints_path,
            # application_base.py:744) — skip HF conversion + re-quantization
            if not os.path.isdir(tc.quantized_checkpoints_path):
                raise FileNotFoundError(
                    f"quantized_checkpoints_path={tc.quantized_checkpoints_path!r}"
                    " does not exist; run save_quantized_state_dict first or"
                    " unset it to quantize online from the HF checkpoint"
                )
            from nxdi_tpu.ops import quantization as quant_ops

            sd = ckpt.load_state_dict(tc.quantized_checkpoints_path)
            params = quant_ops.unflatten_params(sd)
            quant_ops.validate_quantized_params(params, tc)
            if tc.lora_config is not None:
                params = self._attach_lora(params)
            return params
        sd = self.get_state_dict()
        params = self.family.convert_hf_state_dict(sd, self.config)
        params = maybe_quantize_params(params, tc)
        if tc.lora_config is not None:
            params = self._attach_lora(params)
        return params

    # -- LoRA serving (reference: modules/lora_serving/, wrap_model_with_lora
    # model_base.py:144) --
    def _attach_lora(self, params):
        from nxdi_tpu.lora import AdapterCache, attach_lora_buffers

        arch = self.family.build_arch(self.config)
        lc = self.tpu_config.lora_config
        params = attach_lora_buffers(params, arch, lc)
        self.adapter_cache = AdapterCache(self.config, arch, lc)
        if lc.lora_ckpt_paths:
            for name, path in lc.lora_ckpt_paths.items():
                self.adapter_cache.register(name, path)
                _, params = self.adapter_cache.ensure(name, params)
        return params

    def set_lora_adapter(self, name: str, path_or_sd=None, adapter_cfg=None) -> int:
        """Dynamic multi-LoRA: make ``name`` resident on device (LRU-evicting
        if slots are full) and return its adapter id for ``generate``
        (reference: AdapterCache swap, lora_serving/lora_model.py:293)."""
        if getattr(self, "adapter_cache", None) is None:
            raise RuntimeError("LoRA serving is not enabled (set lora_config)")
        if path_or_sd is not None:
            self.adapter_cache.register(name, path_or_sd, adapter_cfg)
        slot, params = self.adapter_cache.ensure(name, self.params)
        # the swapped buffers come back as held by default: lay them out as
        # every program takes them (never on a call)
        from nxdi_tpu.runtime import param_layout

        self.params = param_layout.move(params, self.param_formats)
        return slot

    @property
    def param_formats(self):
        """The formats every program takes the params in (None: as held)."""
        return None if self.param_layout is None else self.param_layout.formats

    def lora_adapter_id(self, name: str) -> int:
        """Adapter id for a resident adapter (0 = base model)."""
        if name is None:
            return 0
        return self.adapter_cache.slot_of[name]

    def save_quantized_state_dict(self, path: str) -> None:
        """Offline weight quantization artifact (reference:
        application_base.py:744 ``save_quantized_state_dict``): quantize the
        converted params pytree and save it flat as safetensors for fast reload
        via ``quantized_checkpoints_path``. A LOADED app saves its in-memory
        params instead — that is what preserves calibrated static-activation
        input scales (ops/quantization.calibrate_app_input_scales)."""
        from nxdi_tpu.ops import quantization as quant_ops

        if self.is_loaded:
            qparams = self.params
        else:
            sd = self.get_state_dict()
            params = self.family.convert_hf_state_dict(sd, self.config)
            qparams = maybe_quantize_params(params, self.tpu_config)
        flat = quant_ops.flatten_params(qparams)
        os.makedirs(path, exist_ok=True)
        ckpt.save_state_dict_safetensors(flat, path)

    # -- overridable pytree layouts (multi-model apps override all three and
    # must apply maybe_quantize_* to each sub-pytree themselves) --
    def param_specs(self):
        specs = self.family.param_specs(self.config)
        if self.tpu_config.lora_config is not None:
            from nxdi_tpu.lora import lora_spec_update

            specs = lora_spec_update(specs, self.tpu_config.lora_config)
        return maybe_quantize_specs(specs, self.tpu_config)

    def _interleaved_window_split(self, arch=None, family=None, config=None):
        """(n_full, n_window) when the cache splits into full + ring stacks
        (window_sized_kv on an interleaved-SWA arch), else None (reference:
        per-layer window-sized caches, gpt_oss_kv_cache_manager.py). Flags are
        read from the PASSED config's tpu_config — a fused-spec draft follows
        its own window settings, not the target's."""
        config = config or self.config
        if not getattr(config.tpu_config, "window_sized_kv", False):
            return None
        arch = arch or (family or self.family).build_arch(config)
        pat = getattr(arch, "kv_window_pattern", None)
        if not pat or all(pat) or not any(pat):
            return None  # homogeneous stacks keep the single-layout path
        return (sum(not w for w in pat), sum(bool(w) for w in pat))

    def cache_partition_specs(self):
        arch = self.family.build_arch(self.config)
        if getattr(arch, "mla", None) is not None:
            # MLA latent cache, contiguous or paged, has ONE shared kv head;
            # nothing to shard on the head axis — replicate (sequence sharding
            # comes with flash decode)
            from jax.sharding import PartitionSpec as P

            return {"k": P(), "v": P()}
        if self.tpu_config.is_block_kv_layout:
            return block_kv_cache_partition_spec()
        specs = dict(kv_cache_partition_spec(self.tpu_config))
        if self._interleaved_window_split(arch) is not None:
            specs["k_win"] = specs["k"]
            specs["v_win"] = specs["v"]
        return specs

    def init_cache_host(self):
        spec = self._cache_spec()
        if isinstance(spec, BlockKVCacheSpec):
            return init_block_kv_cache(spec)
        cache = init_kv_cache(spec)
        ring = self._ring_cache_spec()
        if ring is not None:
            win = init_kv_cache(ring)
            cache["k_win"], cache["v_win"] = win["k"], win["v"]
        return cache

    def _ring_cache_spec(self, family=None, config=None):
        """Ring-stack spec for the window layers of an interleaved split."""
        import dataclasses

        family = family or self.family
        config = config or self.config
        arch = family.build_arch(config)
        split = self._interleaved_window_split(arch, config=config)
        if split is None:
            return None
        base = self._cache_spec(family, config)
        tc = config.tpu_config
        return dataclasses.replace(
            base,
            num_layers=split[1],
            max_len=min(tc.window_ring_slots, tc.seq_len),
        )

    # ------------------------------------------------------------------
    def compile(self, compiled_model_path: str) -> None:
        """AOT-compile every (submodel, bucket) program into the persistent
        compilation cache and save the config to ``compiled_model_path``
        (reference: application_base.py:292)."""
        t0 = time.time()
        os.makedirs(compiled_model_path, exist_ok=True)
        self.config.save(compiled_model_path)
        enable_persistent_cache()
        self._build_wrappers()
        params_struct = self.build_params_struct()
        cache_struct = self._cache_struct()
        for wrapper in self.models.values():
            wrapper.aot_compile(params_struct, cache_struct)
        logger.info("compiled %d submodels in %.1fs", len(self.models), time.time() - t0)

    def build_params_struct(self):
        """Abstract param pytree (no weight IO) for AOT lowering."""
        arch = self.family.build_arch(self.config)
        struct = params_shape_struct(self.family, self.config, arch)
        if self.tpu_config.lora_config is not None:
            from nxdi_tpu.lora import lora_shape_struct

            struct = lora_shape_struct(struct, arch, self.tpu_config.lora_config)
        return maybe_quantize_struct(struct, self.tpu_config)

    def _cache_struct(self):
        spec = self._cache_spec()
        shape_v = getattr(spec, "shape_v", spec.shape)
        struct = {
            "k": jax.ShapeDtypeStruct(spec.shape, spec.store_dtype),
            "v": jax.ShapeDtypeStruct(shape_v, spec.store_dtype),
        }
        ring = self._ring_cache_spec()
        if ring is not None:  # interleaved window-sized split (AOT parity
            # with init_cache_host — the traced program needs k_win/v_win)
            struct["k_win"] = jax.ShapeDtypeStruct(ring.shape, ring.store_dtype)
            struct["v_win"] = jax.ShapeDtypeStruct(ring.shape_v, ring.store_dtype)
        return struct

    def _cache_spec(self, family=None, config=None):
        family = family or self.family
        config = config or self.config
        arch = family.build_arch(config)
        # window/ring flags must follow the model whose cache this is — a
        # fused-spec DRAFT without sliding windows keeps a full-length cache
        # even when the target runs window_sized_kv
        tc = config.tpu_config
        if tc.is_block_kv_layout:
            heads, k_dim, v_dim = arch.num_kv_heads, arch.head_dim, None
            if getattr(arch, "mla", None) is not None:
                # the paged LATENT pool: one row a token, the rope key (padded
                # to a lane tile) in ``k`` and the normed latent in ``v``
                from nxdi_tpu.ops.mla import paged_latent_widths

                heads = 1
                k_dim, v_dim = paged_latent_widths(arch.mla)
            return BlockKVCacheSpec(
                num_layers=arch.num_layers,
                num_blocks=tc.pa_num_blocks,
                block_size=tc.pa_block_size,
                num_kv_heads=heads,
                head_dim=k_dim,
                dtype=arch.dtype,
                quant_dtype=(tc.kv_quant_config.dtype if tc.kv_quant_config else None),
                v_head_dim=v_dim,
            )
        max_len = tc.seq_len
        split = self._interleaved_window_split(arch, config=config)
        if getattr(tc, "window_sized_kv", False) and split is None:
            # ring layout: W (+ spec lookahead) slots per layer instead of the
            # full budget (reference: window-sized cache shapes
            # kv_cache_manager.py:195)
            max_len = min(max_len, tc.window_ring_slots)
        if split is not None:
            # interleaved split: this spec covers the FULL-attention layers
            # only; the window layers live in the ring stack (_ring_cache_spec)
            import dataclasses

            spec = arch.kv_cache_spec(
                tc.kv_cache_batch_size + tc.kv_cache_padding_size,
                max_len,
                quant_dtype=(tc.kv_quant_config.dtype if tc.kv_quant_config else None),
            )
            return dataclasses.replace(spec, num_layers=split[0])
        return arch.kv_cache_spec(
            tc.kv_cache_batch_size + tc.kv_cache_padding_size,
            max_len,
            quant_dtype=(
                tc.kv_quant_config.dtype if tc.kv_quant_config else None
            ),
        )

    # ------------------------------------------------------------------
    def load(self, compiled_model_path: Optional[str] = None) -> None:
        """Weights to HBM (sharded), KV cache allocated, programs built, warmup
        (reference: application_base.py:317-372)."""
        if compiled_model_path is not None:
            enable_persistent_cache()
        self.mesh = mesh_from_config(self.tpu_config)
        self._build_wrappers()

        params_host = self.build_params()
        arch = self.family.build_arch(self.config)
        if getattr(getattr(arch, "moe", None), "per_phase_hybrid", False):
            # decode regime gets its own EP-heavy sharded expert copy
            # (reference: hybrid preshard-hook weight duplication)
            from nxdi_tpu.ops.moe import duplicate_per_phase_experts

            params_host = duplicate_per_phase_experts(params_host)
        self.params = shard_pytree(params_host, self.param_specs(), self.mesh)
        del params_host

        cache_host = self.init_cache_host()
        self.kv_cache = shard_pytree(cache_host, self.cache_partition_specs(), self.mesh)
        # the weights in the layout the token-generation program's dots read,
        # laid out once here; every program takes them pinned to it
        from nxdi_tpu.runtime import param_layout

        self.param_layout = param_layout.lay_out(self)
        self.telemetry.record_param_layout(
            len(self.param_layout.moved), self.param_layout.moved_bytes
        )
        logger.info(param_layout.describe(self.param_layout))

        if not self.tpu_config.skip_warmup:
            self.warmup()
            # warmup compiled every (submodel, bucket, steps) program: any
            # lowering from here on is a mid-serving retrace — the guard
            # warns/raises per TpuConfig.retrace_guard. skip_warmup apps
            # compile lazily by design, so the guard is never sealed there.
            self.retrace_guard.seal()
        from nxdi_tpu.utils.snapshot import maybe_attach_from_env

        maybe_attach_from_env(self)  # reference-style env-driven snapshotting
        # cost observatory (analysis/costs.py): every export divides the
        # measured dispatch latencies through this app's per-program
        # CostSheets into the nxdi_program_mfu_pct / nxdi_program_hbm_bw_pct
        # / nxdi_roofline_gap_ratio gauges, and the sheet table rides the
        # JSON snapshot as _cost_sheets
        from nxdi_tpu.analysis.costs import attach_cost_gauges

        attach_cost_gauges(self)
        # numerics sentinel (telemetry/sentinel.py): adopt the app so the
        # compiled-in logit-health stats record on EVERY host path (static
        # generate and serving alike); the serving engine later binds its
        # flight recorder for postmortem capture and replay verification
        if self.tpu_config.sentinel is not None and self.telemetry.enabled:
            from nxdi_tpu.telemetry.sentinel import NumericsSentinel

            sentinel = NumericsSentinel(
                self.telemetry, self.tpu_config.sentinel, app=self
            )
            self.telemetry.attach_sentinel(sentinel)
            # warm the replay probe NOW (params are resident): the first
            # replay must never stall a serving step on a probe compile
            sentinel.prepare()
        elif self.tpu_config.sentinel is not None:
            logger.warning(
                "TpuConfig(sentinel=...) declared but telemetry is off — "
                "the numerics sentinel records through the metrics "
                "registry; nothing will be observed"
            )
        self.is_loaded = True

    def _build_wrappers(self) -> None:
        if self.models:
            return
        self.enable_models()
        if self.mesh is None:
            self.mesh = mesh_from_config(self.tpu_config)
        if getattr(self, "retrace_guard", None) is None:
            from nxdi_tpu.analysis import RetraceGuard

            self.retrace_guard = RetraceGuard(
                mode=getattr(self.tpu_config, "retrace_guard", "warn"),
                telemetry=self.telemetry,
            )
        param_shardings = sharding_tree(self.param_specs(), self.mesh)
        cache_shardings = sharding_tree(self.cache_partition_specs(), self.mesh)
        for wrapper in self.models.values():
            wrapper.retrace_guard = self.retrace_guard
            wrapper.telemetry = self.telemetry
            wrapper.build(self.mesh, param_shardings, cache_shardings)

    def warmup(self) -> None:
        """Run every compiled program once on dummy inputs so first real
        requests never hit compile latency (reference: application_base.py:348).
        Each wrapper enumerates its own program grid (buckets; the multi-step
        wrapper also its step rungs — a cold tail rung would otherwise compile
        mid-request)."""
        t0 = time.time()
        for wrapper in self.models.values():
            for batch in wrapper.warmup_batches():
                out, self.kv_cache = wrapper.forward(self.params, self.kv_cache, batch)
                jax.block_until_ready(out)
        logger.info("warmup done in %.1fs", time.time() - t0)

    def reset_kv_cache(self) -> None:
        from nxdi_tpu.kvcache.kv_cache import reset_kv_cache

        self.kv_cache = reset_kv_cache(self.kv_cache)

    def audit(self, **kwargs):
        """Run the static program auditor over this app's compiled submodels
        (nxdi_tpu/analysis): donation, collective budget, dtype drift, baked
        constants, required kernel strategies. Weights are NOT required —
        auditing traces/lowers from abstract structs like aot_compile."""
        from nxdi_tpu.analysis import audit_application

        return audit_application(self, **kwargs)


def params_shape_struct(family, config, arch):
    """Build a ShapeDtypeStruct pytree matching the family's params layout
    without touching checkpoint bytes — used for AOT compile before weights
    exist (reference compiles from checkpoint_loader_fn lazily too,
    application_base.py:628)."""
    if hasattr(family, "param_shape_struct"):
        return family.param_shape_struct(config)
    from nxdi_tpu.models import dense

    return dense.param_shape_struct(config, arch)


class TpuModelForCausalLM(ApplicationBase):
    """CausalLM application: CTE + TKG submodels, CPU-side dispatch
    (reference: models/model_base.py:3078 ``NeuronBaseForCausalLM``)."""

    def enable_models(self) -> None:
        arch = self.family.build_arch(self.config)
        inv_freq = self.family.build_inv_freq(self.config)
        tc = self.tpu_config
        # per-phase hybrid MoE: the decode submodel compiles EP-heavy via a
        # per-submodel arch override (reference: per-phase moe process groups,
        # moe_v2.py:135-161; HybridShardingConfig config.py:1060)
        arch_tkg = arch
        if getattr(getattr(arch, "moe", None), "per_phase_hybrid", False):
            import dataclasses

            arch_tkg = dataclasses.replace(
                arch, moe=dataclasses.replace(arch.moe, phase="decode")
            )
        sampling_kwargs = {}
        odsc = tc.on_device_sampling_config
        on_device_sampling = odsc is not None
        if on_device_sampling:
            sampling_kwargs = dict(
                do_sample=odsc.do_sample,
                global_topk=odsc.global_topk,
                deterministic=odsc.deterministic,
                dp_sampling=getattr(odsc, "dp_sampling", False),
            )
        # async (device-resident) loop needs every step to emit the next step's
        # inputs on device; only meaningful with on-device sampling. Multi-step
        # decode chains its windows the same way, so it needs the CTE to emit
        # next_inputs too (window 0 then starts device-resident with the same
        # split-chained rng schedule as the 1-step async loop).
        if (tc.async_mode or tc.decode_steps_per_dispatch > 1) and on_device_sampling:
            sampling_kwargs["return_next_inputs"] = True
        if (
            tc.sentinel is not None
            and tc.sentinel.logit_health
            and self.telemetry.enabled
        ):
            # numerics sentinel (telemetry/sentinel.py): compile the (B, 5)
            # logit-health reduction into every host-path dispatch (CTE,
            # TKG, prefix-prefill) — the sentinel reads it as the
            # nxdi_numerics_* series and the NaN/Inf postmortem trigger.
            # Gated on telemetry like the attach in load(): with telemetry
            # off nothing could observe the stats, so the graph must not
            # pay for them either (load() warns about the combination).
            sampling_kwargs["output_logit_stats"] = True
        if tc.tensor_capture_config is not None:
            # debug intermediates compiled into extra outputs (reference:
            # TensorCaptureConfig, model_base.py:1091-1198)
            sampling_kwargs["tensor_capture"] = tuple(
                tc.tensor_capture_config.capture_points
            )
        tr_extra = {}
        if tc.tensor_replacement_config is not None:
            # captured host tensors compiled back in as extra inputs selected
            # by name+mask (reference: tensor replacement, config.py:1136-1166)
            pts = tuple(tc.tensor_replacement_config.replace_points)
            sampling_kwargs["tensor_replacement"] = pts
            H, L = arch.hidden_size, arch.num_layers
            if "embeds" in pts:
                tr_extra["tr_embeds"] = ((-1, H), np.float32)
                tr_extra["tr_embeds_mask"] = ((), np.float32)
            if "layers" in pts:
                tr_extra["tr_layer_values"] = ((L, -1, H), np.float32)
                tr_extra["tr_layer_mask"] = ((L,), np.float32)
            if "hidden" in pts:
                tr_extra["tr_hidden"] = ((-1, H), np.float32)
                tr_extra["tr_hidden_mask"] = ((), np.float32)

        # prefill/decode disaggregation: a decode-role process never runs a
        # local prefill, so the whole CTE bucket ladder (and prefix-prefill
        # below) stays uncompiled — requests arrive as imported KV chains
        # (serving/handoff.py) and the HBM program footprint shrinks to the
        # decode set. Validation already pinned role-incompatible flags
        # (mixed_dispatch, and decode-only shapes under role='prefill').
        role = getattr(tc, "role", "unified")
        if role != "decode":
            self.models[TAG_CONTEXT_ENCODING] = ModelWrapper(
                TAG_CONTEXT_ENCODING,
                self.config,
                arch,
                inv_freq,
                batch_size=tc.ctx_batch_size,
                n_active_tokens=0,  # bucket-determined
                buckets=autobucketing.context_encoding_buckets(self.config),
                attend_to_cache=False,
                forward_kwargs=dict(
                    gather_last_token=True,
                    output_logits=tc.output_logits,
                    on_device_sampling=on_device_sampling,
                    **sampling_kwargs,
                ),
                extra_inputs=tr_extra,
            )
        self.models[TAG_TOKEN_GENERATION] = ModelWrapper(
            TAG_TOKEN_GENERATION,
            self.config,
            arch_tkg,
            inv_freq,
            batch_size=tc.tkg_batch_size,
            n_active_tokens=1,
            buckets=autobucketing.token_generation_buckets(self.config),
            attend_to_cache=True,
            forward_kwargs=dict(
                gather_last_token=False,
                output_logits=tc.output_logits,
                on_device_sampling=on_device_sampling,
                **sampling_kwargs,
            ),
            extra_inputs=tr_extra,
        )
        if tc.decode_steps_per_dispatch > 1:
            # multi-step decode: K chained TKG steps per dispatch (models/
            # base.py multi_step_token_gen). The plain TKG submodel stays —
            # it is the 1-step program the host falls back to (logits
            # processors, >8 eos ids) and the async chain's building block.
            self.models[TAG_TOKEN_GENERATION_MULTISTEP] = MultiStepTKGWrapper(
                TAG_TOKEN_GENERATION_MULTISTEP,
                self.config,
                arch_tkg,
                inv_freq,
                batch_size=tc.tkg_batch_size,
                n_active_tokens=1,
                buckets=autobucketing.token_generation_buckets(self.config),
                attend_to_cache=True,
                steps_ladder=autobucketing.multistep_step_ladder(
                    tc.decode_steps_per_dispatch
                ),
                forward_kwargs=dict(
                    do_sample=odsc.do_sample,
                    global_topk=odsc.global_topk,
                    deterministic=odsc.deterministic,
                    dp_sampling=getattr(odsc, "dp_sampling", False),
                ),
            )
        if tc.device_loop:
            # device-resident decode loop: a while_loop running one full
            # decode step per iteration with per-row EOS + budget exit
            # applied in-graph (models/base.py device_loop_token_gen). The
            # plain TKG (and any multistep) submodels stay — they are the
            # host fallbacks for >8 eos ids and the 1-2 token tails below
            # the cap ladder's floor.
            outfeed = tc.device_loop_outfeed
            if outfeed is None:
                # auto: stream on real accelerators; buffered whole-result
                # on CPU/interpret (the exact tier-1 surface)
                outfeed = jax.default_backend() not in ("cpu",)
            self.models[TAG_DEVICE_LOOP] = DeviceLoopTKGWrapper(
                TAG_DEVICE_LOOP,
                self.config,
                arch_tkg,
                inv_freq,
                batch_size=tc.tkg_batch_size,
                n_active_tokens=1,
                buckets=autobucketing.token_generation_buckets(self.config),
                attend_to_cache=True,
                cap_ladder=autobucketing.device_loop_budget_ladder(
                    tc.device_loop_fence or tc.seq_len
                ),
                outfeed_enabled=bool(outfeed),
                forward_kwargs=dict(
                    do_sample=odsc.do_sample,
                    global_topk=odsc.global_topk,
                    deterministic=odsc.deterministic,
                    dp_sampling=getattr(odsc, "dp_sampling", False),
                ),
            )
        if (tc.is_prefix_caching or tc.is_chunked_prefill) and role != "decode":
            # multi-token prefill that attends the cache: the new chunk/suffix
            # sees the cached prefix through the block table (reference:
            # prefix-caching CTE with 2-D buckets, model_wrapper.py:918;
            # chunked prefill ChunkedPrefillConfig config.py:1042)
            self.models[TAG_PREFIX_PREFILL] = ModelWrapper(
                TAG_PREFIX_PREFILL,
                self.config,
                arch,
                inv_freq,
                batch_size=tc.ctx_batch_size,
                n_active_tokens=0,
                buckets=autobucketing.prefix_prefill_buckets(self.config),
                attend_to_cache=True,
                prefill_to_cache=True,
                forward_kwargs=dict(
                    gather_last_token=True,
                    output_logits=tc.output_logits,
                    on_device_sampling=on_device_sampling,
                    **sampling_kwargs,
                ),
                extra_inputs=tr_extra,
            )
        if tc.mixed_dispatch:
            # unified mixed prefill+decode dispatch: one program per
            # TOTAL-packed-token bucket serves a whole serving step (prefill
            # chunks + decode singles in one flat stream) through the ragged
            # paged-attention kernel (ops/kernels/ragged_paged_attention)
            mixed_kwargs = dict(sampling_kwargs)
            # rows enter and leave the packed batch between steps, so the
            # next step is always host-assembled — the device-resident
            # next_inputs chain assumes the per-row (B,) contract
            mixed_kwargs.pop("return_next_inputs", None)
            self.models[TAG_MIXED] = MixedModelWrapper(
                TAG_MIXED,
                self.config,
                arch,
                inv_freq,
                batch_size=1,
                n_active_tokens=0,  # bucket-determined (packed token count)
                buckets=autobucketing.mixed_token_buckets(self.config),
                attend_to_cache=True,
                prefill_to_cache=True,
                num_rows=tc.tkg_batch_size,
                forward_kwargs=dict(
                    gather_last_token=True,
                    mixed_rows=True,
                    output_logits=tc.output_logits,
                    on_device_sampling=on_device_sampling,
                    **mixed_kwargs,
                ),
                extra_inputs=dict(tr_extra),
            )
            if self.telemetry.enabled:
                self.telemetry.seed_mixed_buckets(
                    self.models[TAG_MIXED].buckets
                )

    @property
    def mixed_supported(self) -> bool:
        return TAG_MIXED in self.models

    # -- dispatch (reference: model_base.py:3606 _get_model_outputs) --
    def forward(
        self,
        input_ids: np.ndarray,
        position_ids: np.ndarray,
        submodel: Optional[str] = None,
        **kwargs,
    ):
        if not self.is_loaded:
            raise RuntimeError("call load() before forward()")
        if submodel is None:
            is_prefill = input_ids.shape[1] > 1
            # a prefill whose first position is nonzero continues an existing
            # context -> prefix/chunked prefill submodel
            if is_prefill and TAG_PREFIX_PREFILL in self.models and position_ids[:, 0].max() > 0:
                submodel = TAG_PREFIX_PREFILL
            else:
                submodel = TAG_CONTEXT_ENCODING if is_prefill else TAG_TOKEN_GENERATION
        if submodel not in self.models:
            raise KeyError(
                f"submodel {submodel!r} is not compiled in this app (role="
                f"{getattr(self.tpu_config, 'role', 'unified')!r}, available: "
                f"{sorted(self.models)})"
            )
        batch = {"input_ids": input_ids, "position_ids": position_ids, **kwargs}
        outputs, self.kv_cache = self.models[submodel].forward(
            self.params, self.kv_cache, batch
        )
        return outputs

    def token_gen_device(self, device_batch, total_len: int):
        """Async hot path: TKG step with device-resident inputs
        (reference: causal_lm_async_execution async_execution.py:190)."""
        outputs, self.kv_cache = self.models[TAG_TOKEN_GENERATION].forward_device(
            self.params, self.kv_cache, device_batch, total_len
        )
        return outputs

    @property
    def multistep_supported(self) -> bool:
        return TAG_TOKEN_GENERATION_MULTISTEP in self.models

    def token_gen_multistep(self, batch_np):
        """Host-path multi-step dispatch: pads inputs, retires K tokens."""
        w = self.models[TAG_TOKEN_GENERATION_MULTISTEP]
        outputs, self.kv_cache = w.forward(self.params, self.kv_cache, batch_np)
        return outputs

    def token_gen_multistep_device(self, device_batch, total_len: int, steps=None):
        """Device-resident multi-step window: K tokens per dispatch, windows
        chained through next_inputs with no host round trip."""
        w = self.models[TAG_TOKEN_GENERATION_MULTISTEP]
        outputs, self.kv_cache = w.forward_device(
            self.params, self.kv_cache, device_batch, total_len, steps=steps
        )
        return outputs

    @property
    def device_loop_supported(self) -> bool:
        return TAG_DEVICE_LOOP in self.models

    def token_gen_device_loop(self, batch_np):
        """One resident-loop launch: pads inputs, runs the while_loop to
        per-row EOS/budget exhaustion, retires up to cap tokens per row.
        Outputs carry ``tokens`` (b, cap) and ``loop_iters``."""
        w = self.models[TAG_DEVICE_LOOP]
        outputs, self.kv_cache = w.forward(self.params, self.kv_cache, batch_np)
        return outputs

    @property
    def async_supported(self) -> bool:
        tc = self.tpu_config
        return (
            tc.async_mode
            and tc.on_device_sampling_config is not None
            and tc.ctx_batch_size == tc.tkg_batch_size
        )
