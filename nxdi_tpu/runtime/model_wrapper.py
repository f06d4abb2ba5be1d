"""Per-submodel façade: bucketed jitted programs + CPU-side pad/dispatch.

The analog of the reference's ``ModelWrapper`` (models/model_wrapper.py:47):
one instance per submodel tag (context_encoding_model, token_generation_model,
speculation_model, ...), owning
  - the bucket ladder and one jitted/AOT-compiled program per bucket,
  - input padding to the bucket's static shape (pad_inputs :725),
  - bucket selection (get_target_bucket :826),
  - batch padding with first-batchline repetition (_forward_with_pad :569).

TPU-native difference: a "compiled program" is ``jax.jit`` of the pure forward
closed over (arch, bucket shape, flags), with params/cache shardings bound and
the KV cache donated. Dispatch is async by default (JAX returns futures), which
subsumes most of the reference's async_execution machinery.
"""

from __future__ import annotations

import contextlib
import itertools
import logging
import re
import secrets
from functools import partial
from typing import Any, Callable, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from nxdi_tpu.kvcache.kv_cache import BlockKVLayout, ContiguousKVLayout
from nxdi_tpu.models.base import causal_lm_forward
from nxdi_tpu.ops import attention_select
from nxdi_tpu.runtime import autobucketing, faults
from nxdi_tpu.runtime.padding import pad_with_first_batchline


def kv_layout_from_config(tc, arch=None):
    """The KV layout every submodel of this app compiles against
    (reference: config flags is_block_kv_layout / is_continuous_batching,
    models/config.py:278-283). Scaled fp8 KV (scale_mode="per_tensor",
    kv_cache_manager.py:642-692) rides the layout as static scales.

    ``window_sized_kv`` on an INTERLEAVED-SWA arch (kv_window_pattern with
    both kinds) keeps the contiguous layout as primary: only the window
    layers ride the W-slot ring stack, assembled per layer inside
    run_decoder_layers' unit scan (reference: gpt_oss_kv_cache_manager.py)."""
    kvq = tc.kv_quant_config
    scales = {}
    if kvq is not None and kvq.scale_mode == "per_tensor":
        scales = {"k_scale": kvq.k_scale, "v_scale": kvq.v_scale}
    elif kvq is not None and kvq.scale_mode in ("per_key", "per_channel"):
        # per-layer array scale buffers ride the frozen layout as nested
        # tuples (hashable); kv_cache.py selects the active layer's row via
        # the in-scan layer index (reference: PER_KEY/PER_CHANNEL scale
        # ParameterLists, kv_cache_manager.py:642-667)
        if arch is not None:
            want = (
                (arch.num_layers, arch.num_kv_heads)
                if kvq.scale_mode == "per_key"
                else (arch.num_layers, arch.head_dim)
            )
            for name, arr in (("k_scales", kvq.k_scales), ("v_scales", kvq.v_scales)):
                if tuple(arr.shape) != want:
                    raise ValueError(
                        f"kv quant {name} shape {tuple(arr.shape)} does not "
                        f"match this model's {kvq.scale_mode} shape {want} — "
                        "recalibrate (kvcache.calibration) for this model"
                    )
        scales = {
            "k_scales": tuple(map(tuple, kvq.k_scales.tolist())),
            "v_scales": tuple(map(tuple, kvq.v_scales.tolist())),
            "scale_axis": "key" if kvq.scale_mode == "per_key" else "channel",
        }
    if tc.is_block_kv_layout:
        return BlockKVLayout(block_size=tc.pa_block_size, **scales)
    if getattr(tc, "window_sized_kv", False):
        from nxdi_tpu.kvcache.kv_cache import WindowKVLayout

        if scales:
            raise NotImplementedError(
                "scaled fp8 KV is not wired into the window ring layout yet"
            )
        pat = getattr(arch, "kv_window_pattern", None) if arch is not None else None
        if pat is not None and not any(pat):
            raise ValueError(
                "window_sized_kv is set but no layer of this model uses "
                "sliding-window attention — a ring cache would silently "
                "truncate full-attention history; unset window_sized_kv"
            )
        if pat and any(pat) and not all(pat):
            return ContiguousKVLayout(route_by_seq_id=tc.is_continuous_batching)
        return WindowKVLayout(
            window=tc.window_ring_slots, route_by_seq_id=tc.is_continuous_batching
        )
    if tc.is_continuous_batching:
        return ContiguousKVLayout(route_by_seq_id=True, **scales)
    return ContiguousKVLayout(**scales)

# --- around a fault of the runtime's persistent compilation cache -----------
# jax 0.9.0 / libtpu 0.0.34 (seen on `TPU v5 lite`, PR 22): an executable
# SERVED FROM THE PERSISTENT CACHE in a later process reports — and labels the
# arrays it returns with — the DEFAULT memory layout, whatever layout it was
# compiled for and really reads and writes (scripts/relayout_cache_probe.py
# shows it with a three-line program). Where the compiler's AUTO choice for
# the KV cache IS the default (the contiguous D=64 cache) nothing goes wrong
# and such programs are cached like any other. The paged pool's is not (the
# compiler wants D minor, the default puts the slot dim there), and a served
# paged program fails its first hand-over. So two kinds of program stay out
# of that cache — compiled under a name no entry has (the module name is part
# of the key), in a window in which nothing is written: the layout-changing
# identity below, and every step program over the block KV layout
# (_AutoLayoutProgram(persist=False)); they cost their compile in each process.
# The name still says what the program is: ``<label>__<process token>_<n>``,
# e.g. ``token_generation_model_4096__3fa9c2d41b07_2``, is the XLA module
# ``jit_token_generation_model_4096__...`` in a profiler trace.
_PROCESS_TOKEN = secrets.token_hex(6)
_UNCACHED_NAMES = itertools.count()


def _sanitised(label: str) -> str:
    """``token_generation_model[4096]`` -> ``token_generation_model_4096``:
    what of a label a function (and so an XLA module) name may carry."""
    return re.sub(r"[^A-Za-z0-9]+", "_", label).strip("_")


@contextlib.contextmanager
def _outside_the_persistent_cache(label: str):
    """Yields a program name that starts with the sanitised ``label`` and
    that no persistent-cache entry has or will have; what compiles inside
    the block is not written to the cache either.

    Inside the block JAX keeps whole tracebacks in the program's locations
    (its own default). ``enable_persistent_cache`` turns that off so that a
    cached program's key does not depend on who called ``compile()`` — and
    with it off JAX 0.9.0 also drops the name stack from every operation:
    ``jax.named_scope`` regions and a Pallas kernel's ``name=`` never reach
    the HLO (the paged decode kernel is the instruction ``tpu_custom_call.3``
    in a trace, not ``paged_attention_decode.3``). A program that no cache
    entry will ever hold has no key to keep stable, so it keeps its names."""
    was = jax.config.jax_persistent_cache_min_compile_time_secs
    tracebacks = jax.config.jax_include_full_tracebacks_in_locations
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1e9)
    jax.config.update("jax_include_full_tracebacks_in_locations", True)
    try:
        yield f"{_sanitised(label)}__{_PROCESS_TOKEN}_{next(_UNCACHED_NAMES)}"
    finally:
        jax.config.update("jax_persistent_cache_min_compile_time_secs", was)
        jax.config.update("jax_include_full_tracebacks_in_locations", tracebacks)


def _named(fn, name: str):
    def call(*args):
        return fn(*args)

    call.__name__ = name
    return call


# (from format, to format, shape, dtype) -> compiled layout-changing identity
_RELAYOUTS: Dict[Any, Callable] = {}


def _relayout(a, fmt):
    """``a`` in the memory layout ``fmt`` — ``jax.device_put(a, fmt)`` through
    an identity program that never touches the persistent cache."""
    key = (a.format, fmt, a.shape, a.dtype)
    move = _RELAYOUTS.get(key)
    if move is None:
        what = f"relayout_{a.dtype}_{'x'.join(map(str, a.shape))}"
        with _outside_the_persistent_cache(what) as name:
            move = _RELAYOUTS[key] = (
                jax.jit(_named(lambda x: x, name), out_shardings=fmt)
                .lower(a)
                .compile()
            )
    return move(a)


class _AutoLayoutProgram:
    """Bucket program compiled with AUTO cache layouts (see _make_program):
    lazily lowered on the first concrete call; the cache pytree is moved
    (``_relayout``) into the executable's preferred input formats when (and
    only when) its current layout differs — one relayout at a program
    transition (e.g. prefill -> decode), zero in the steady-state chain."""

    def __init__(self, fn, jit_kwargs, label: str = "?", required_strategies=(),
                 retrace_guard=None, persist: bool = True, telemetry=None):
        self._fn, self._jit_kwargs = fn, jit_kwargs
        self.jitted = jax.jit(fn, **jit_kwargs)
        # False: compile outside the persistent compilation cache (see
        # _outside_the_persistent_cache) — a served executable would be wrong
        self.persist = persist
        self.label = label
        self._compiled = None
        self._cache_formats = None
        # attention strategies the traced program actually chose (reference:
        # FlashAttentionStrategy logging, attention_base.py:1330) — filled at
        # lowering; silent kernel fallbacks become visible and assertable
        self.attention_strategies: tuple = ()
        # (flag_name, acceptable strategy names): enforced after lowering so
        # an enabled kernel flag that never engaged raises instead of
        # silently no-opping (round-3 verdict weak #4)
        self.required_strategies = tuple(required_strategies)
        # the form(s) this program's expert layers took (ops/moe.py
        # expert_form: "dense" / "sorted" / "compact"; () = no expert layer) — filled at
        # lowering like the strategies, and counted once per lowering into
        # ``telemetry``'s nxdi_moe_expert_form_programs_total
        self.expert_forms: tuple = ()
        self.telemetry = telemetry
        # app-owned analysis.RetraceGuard: every actual lowering is reported
        # so a (re)trace after serving starts is caught per TpuConfig
        self.retrace_guard = retrace_guard

    def _lower(self, *args):
        """The ONE lowering path — AOT artifact (`compile`) and lazy
        first-call (`__call__`) both come through here, so required-strategy
        verification and retrace-guard recording provably run on both."""
        from nxdi_tpu.ops import moe as moe_ops

        if self.retrace_guard is not None:
            self.retrace_guard.record(self.label)
        attention_select._STRATEGY_TRACE.clear()
        moe_ops._FORM_TRACE.clear()
        lowered = self.jitted.lower(*args)
        self._snap_strategies()
        self._snap_expert_forms(moe_ops)
        return lowered

    def _snap_expert_forms(self, moe_ops):
        if moe_ops._FORM_TRACE:  # empty: no expert layer, or a tracing-cache hit
            self.expert_forms = tuple(sorted(set(moe_ops._FORM_TRACE)))
        if self.telemetry is not None and self.telemetry.enabled:
            for form in self.expert_forms:
                self.telemetry.record_expert_form(self.label.partition("[")[0], form)

    def _snap_strategies(self):
        if not attention_select._STRATEGY_TRACE:
            # jaxpr-tracing cache hit: the python body (and its recording)
            # did not re-run — keep the strategies from the first lowering
            return
        self.attention_strategies = tuple(attention_select._STRATEGY_TRACE)
        logging.getLogger("nxdi_tpu").info(
            "%s attention strategies: %s",
            self.label,
            ",".join(self.attention_strategies),
        )
        from nxdi_tpu.analysis.checkers import (
            missing_required_strategies,
            required_strategy_error,
        )

        for flag, names in missing_required_strategies(
            self.attention_strategies, self.required_strategies
        ):
            raise RuntimeError(required_strategy_error(self.label, flag, names))

    def compile(self, *args):
        """Lower and compile for abstract ``args`` — through the persistent
        compilation cache, or around it where a served executable would be
        wrong (``persist=False``)."""
        if self.persist:
            return self._lower(*args).compile()
        with _outside_the_persistent_cache(self.label) as name:
            self.jitted = jax.jit(_named(self._fn, name), **self._jit_kwargs)
            return self._lower(*args).compile()

    def __call__(self, params, cache, batch):
        if self._compiled is None:
            # AUTO layouts resolve at compile time, so lowering must see
            # ABSTRACT args (concrete arrays carry a fixed layout and trip
            # jit's layout check)
            absargs = jax.tree_util.tree_map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=a.sharding),
                (params, cache, batch),
            )
            self._compiled = self.compile(*absargs)
            self._cache_formats = self._compiled.input_formats[0][1]
        flat, treedef = jax.tree_util.tree_flatten(cache)
        fmts = jax.tree_util.tree_leaves(self._cache_formats)
        moved = [
            a if a.format == f else _relayout(a, f)
            for a, f in zip(flat, fmts)
        ]
        cache = jax.tree_util.tree_unflatten(treedef, moved)
        return self._compiled(params, cache, batch)


@jax.jit
def decode_next_ids(prev_tokens, prev_rows, host_ids):
    """``input_ids`` of a decode step whose previous step's tokens are still
    on the device: row i takes ``prev_tokens[prev_rows[i]]``, or its
    ``host_ids`` row where ``prev_rows[i] < 0`` (a row that joined since: its
    last token came from its prefill's fetch). A program of its own ahead of
    the token-generation program, whose name and time it must not carry."""
    took = prev_tokens[jnp.maximum(prev_rows, 0), :1].astype(host_ids.dtype)
    return jnp.where(prev_rows[:, None] >= 0, took, host_ids)


#: Telemetry.phase of a wrapper with no (or disabled) telemetry
_NO_PHASE = contextlib.nullcontext()

TAG_CONTEXT_ENCODING = "context_encoding_model"
TAG_TOKEN_GENERATION = "token_generation_model"
TAG_TOKEN_GENERATION_MULTISTEP = "tkg_multistep"
TAG_DEVICE_LOOP = "tkg_device_loop"
TAG_SPECULATION = "speculation_model"
TAG_FUSED_SPECULATION = "fused_speculation_model"
TAG_MEDUSA_SPECULATION = "medusa_speculation_model"
TAG_MIXED = "mixed_model"

# fixed width of the multi-step decode program's eos_token_ids input (HF eos
# lists are ints or short lists; the host falls back to 1-step decode beyond)
MULTISTEP_EOS_SLOTS = 8


def normalize_program_key(key):
    """``(bucket, steps)`` from a program key — THE one place that knows
    plain wrappers key on the bucket int and the multi-step wrapper on
    ``(steps, bucket)`` (shared by ``iter_programs`` and the cost
    observatory's sheet labeling)."""
    if isinstance(key, tuple):
        return int(key[1]), int(key[0])
    return int(key), 1


def decode_window_limit(tpu_config, models) -> int:
    """Largest KV position the compiled decode programs can serve: the device
    drops KV writes beyond the largest compiled TKG bucket, not just beyond
    seq_len (shared by the host decode loops that clamp retirement).

    A prefill-only app (no cache-attending submodel) is limited by seq_len
    alone — guarded explicitly because ``min(x, *())`` is a TypeError.

    Wrappers whose buckets are NOT KV windows (``window_buckets = False``,
    i.e. the mixed wrapper's total-packed-token ladder) are excluded: their
    rungs say nothing about how much KV a program can attend."""
    tops = [
        w.buckets[-1]
        for w in models.values()
        if w.attend_to_cache and getattr(w, "window_buckets", True)
    ]
    return min([tpu_config.seq_len, *tops])


class ModelWrapper:
    def __init__(
        self,
        tag: str,
        config,  # InferenceConfig
        arch,
        inv_freq: np.ndarray,
        *,
        batch_size: int,
        n_active_tokens: int,
        buckets: Sequence[int],
        attend_to_cache: bool,
        prefill_to_cache: bool = False,
        bucket_strategy: str = "first_fit",
        forward_fn: Optional[Callable] = None,
        forward_kwargs: Optional[Dict[str, Any]] = None,
        extra_inputs: Optional[Dict[str, Any]] = None,
    ) -> None:
        self.tag = tag
        self.config = config
        self.arch = arch
        self.inv_freq = inv_freq
        self.batch_size = batch_size
        self.n_active_tokens = n_active_tokens
        self.buckets = sorted(buckets)
        self.attend_to_cache = attend_to_cache
        # prefix-cached / chunked prefill: multi-token input (bucketed on its
        # length like CTE) that ALSO attends the cache — the suffix sees the
        # prefix through the block table (reference: perform_prefix_prefill
        # attention_base.py:909, chunked :1083)
        self.prefill_to_cache = prefill_to_cache
        if prefill_to_cache and getattr(arch, "bidirectional_image_attention", False):
            # span ids restart per chunk, so same-image tokens in the cached
            # prefix could never match — reject at app construction instead of
            # silently computing causal-only attention (causal_lm_forward only
            # derives bidir spans for pure-prefill programs now, so this is
            # the loud gate the old in-trace NotImplementedError provided)
            raise ValueError(
                "bidirectional image attention (gemma3-vision) does not "
                "compose with prefix-cached/chunked prefill; disable prefix "
                "caching for this model"
            )
        self.bucket_strategy = bucket_strategy
        self.forward_fn = forward_fn or causal_lm_forward
        self.forward_kwargs = dict(forward_kwargs or {})
        self.layout = kv_layout_from_config(config.tpu_config, arch)
        # extra KV positions a single dispatch may write past the current
        # length (speculation windows); widens bucket selection accordingly
        self.lookahead = 0
        # extra fixed-shape batch inputs beyond the decoder contract, e.g.
        # {"image_embeds": ((num_image_tokens, hidden), jnp.float32)} — shape
        # is WITHOUT the batch dim (reference: multimodal model wrappers take
        # vision inputs, image_to_text_model_wrapper.py:19)
        self.extra_inputs = dict(extra_inputs or {})
        # stochastic sampling needs a per-step PRNG key threaded as an input
        self.needs_rng = bool(self.forward_kwargs.get("do_sample", False))
        self._programs: Dict[int, Callable] = {}
        self._mesh = None
        # latency observability (reference: benchmark.py:468 LatencyCollector
        # registers forward pre/post hooks)
        self.pre_hooks: List[Callable] = []
        self.post_hooks: List[Callable] = []
        # input snapshotting (utils/snapshot.py; reference: snapshot hooks
        # application_base.py:421) — called with (tag, numpy batch) per dispatch
        self.snapshot_hook: Optional[Callable] = None
        # analysis.RetraceGuard shared across the app's wrappers; set by the
        # application before build() so programs report their lowerings
        self.retrace_guard = None
        # serving telemetry (nxdi_tpu/telemetry.Telemetry) shared across the
        # app's wrappers; set by the application in _build_wrappers. Every
        # dispatch records per-(submodel, bucket[, steps]) count + latency +
        # padding waste into its registry.
        self.telemetry = None

    # ------------------------------------------------------------------
    # build: one jitted program per bucket (reference: model_wrapper.py:1442
    # DecoderModelInstance supplies the traced graph per bucket)
    # ------------------------------------------------------------------
    def build(self, mesh, param_shardings, cache_shardings) -> None:
        self._mesh = mesh
        # kept for the AOT artifact path: compile-time lowering must see the
        # same NamedShardings the committed arrays carry at serve time, or
        # the persistent-cache entries never hit
        self._param_shardings = param_shardings
        self._cache_shardings = cache_shardings
        for bucket in self.buckets:
            self._programs[bucket] = self._make_program(
                bucket, mesh, param_shardings, cache_shardings
            )

    @property
    def policy(self):
        """Sharding policy for this submodel's activations (parallel/policy.py:
        SP/CP for prefill, attention-DP/flash-decoding for decode)."""
        from nxdi_tpu.parallel.policy import (
            context_encoding_policy,
            token_generation_policy,
        )

        tc = self.config.tpu_config
        decode_like = self.attend_to_cache and not self.prefill_to_cache
        return (
            token_generation_policy(tc) if decode_like else context_encoding_policy(tc)
        )

    def make_forward(self, bucket: int):
        """The pure (params, cache, batch) -> (outputs, cache) function this
        bucket compiles. Subclasses (fused speculation, ...) override."""
        if self.prefill_to_cache:
            # chunk/suffix prefill: bucket pads the input; attends the cache
            kwargs = dict(attend_to_cache=True, kv_window=None)
        elif self.attend_to_cache:
            # token generation: fixed active tokens, bucket bounds the attended KV window
            kwargs = dict(attend_to_cache=True, kv_window=bucket)
        else:
            # context encoding: bucket IS the padded input length
            kwargs = dict(attend_to_cache=False, kv_window=None)
        kwargs["policy"] = self.policy
        kwargs["layout"] = self.layout
        kwargs.update(self.forward_kwargs)
        return partial(self.forward_fn, self.arch, self.inv_freq, **kwargs)

    def _make_program(self, bucket: int, mesh, param_shardings, cache_shardings):
        fn = self.make_forward(bucket)

        replicated = NamedSharding(mesh, P())
        batch_shardings = {
            "input_ids": replicated,
            "position_ids": replicated,
            "last_token_index": replicated,
            "sampling_params": replicated,
        }
        for key in self._layout_input_keys():
            batch_shardings[key] = replicated
        if self.lora_enabled:
            batch_shardings["adapter_ids"] = replicated
        for key in self.extra_inputs:
            batch_shardings[key] = replicated
        if self.needs_rng:
            batch_shardings["rng"] = replicated
        # params/cache are COMMITTED arrays (device_put with NamedShardings at
        # load), so their shardings are inferred from the args; only the host
        # batch inputs need explicit (replicated) shardings. The CACHE rides
        # with AUTO memory layout: with the default layout pinned, XLA baked
        # full-cache layout-conversion copies into the decode loop's
        # entry/exit — profiled at ~10 ms/step on a 4.3 GB cache (4 copies of
        # bf16[16,16,8,2048,64]). AUTO lets the compiler choose the loop's
        # preferred layout for the I/O buffers; _AutoLayoutProgram relayouts
        # the cache ONCE into that layout and the donated chain then carries
        # it forward with zero copies in steady state.
        from jax.experimental.layout import Format, Layout

        # AUTO layout, PINNED sharding: the sharding invariant must survive
        # the donated round-trip (a drifting output sharding breaks aliasing
        # and re-triggers per-step relayouts — seen with the qwen3_next conv
        # state); only the memory layout is left to the compiler
        pinned = self._pinned_cache_layouts()
        auto = jax.tree_util.tree_map_with_path(
            lambda path, sh: Format(
                pinned.get(getattr(path[-1], "key", None), Layout.AUTO), sh
            ),
            cache_shardings,
        )
        return _AutoLayoutProgram(
            fn,
            dict(
                in_shardings=(None, auto, batch_shardings),
                out_shardings=(None, auto),
                donate_argnums=(1,),
            ),
            label=f"{self.tag}[{bucket}]",
            required_strategies=self._required_strategies(),
            retrace_guard=self.retrace_guard,
            persist=not isinstance(self.layout, BlockKVLayout),
            telemetry=self.telemetry,
        )

    def _required_strategies(self):
        """Kernel flags this program MUST engage (checked post-lowering).
        Scoped to the default causal-lm forward — custom family forwards
        reject unsupported flags at app construction instead."""
        from nxdi_tpu.models.base import causal_lm_forward as _default_fwd

        if self.forward_fn is not _default_fwd:
            return ()
        tc = self.config.tpu_config
        req = []
        if tc.mlp_kernel_enabled:
            req.append(("mlp_kernel_enabled", ("mlp_fused_kernel",)))
        if tc.qkv_kernel_enabled:
            req.append(("qkv_kernel_enabled", ("qkv_fused_kernel",)))
        elif tc.fused_qkv:
            req.append(("fused_qkv", ("qkv_fused_matmul", "qkv_fused_kernel")))
        return tuple(req)

    def _pinned_cache_layouts(self) -> dict:
        """``{cache leaf: Layout}`` for the leaves whose memory layout is NOT
        left to each program: a store of ring rows a slot (the architecture's
        ``ring_cache_keys``), rows-minor on the TPU. The decode program's
        two-part attention and the commit kernel want it so (ops/kernels/
        kv_commit.py), a prefill only writes a slot's tail into it and would
        pick the default; left to AUTO, every prefill relaid the store twice,
        each time into a new buffer of its size, on a device that is four
        fifths full (PERF.md, PR 35: single steps of 1-3 s in ``enqueue``)."""
        keys = getattr(self.arch, "ring_cache_keys", ())
        plain = getattr(self.arch, "slot_cache_keys", {})  # {leaf: its row-major tiling}
        if not (keys or plain) or not isinstance(self.layout, BlockKVLayout) or self._mesh is None:
            return {}
        if self._mesh.devices.flat[0].platform != "tpu":
            return {}
        from jax.experimental.layout import Layout

        from nxdi_tpu.config import to_jax_dtype

        packed = jnp.dtype(to_jax_dtype(self.arch.dtype)).itemsize == 2
        tiling = ((8, 128), (2, 1)) if packed else ((8, 128),)
        pinned = {k: Layout(major_to_minor=(0, 1, 2, 4, 3), tiling=tiling) for k in keys}
        # any other store a slot (an index, a recurrent state): row-major as it
        # is declared, in the tiling its architecture names, for the same reason
        pinned.update({
            k: Layout(major_to_minor=(0, 1, 2, 3, 4), tiling=tiles) for k, tiles in plain.items()
        })
        return pinned

    @property
    def per_slot_cache(self) -> bool:
        """The cache tree holds a store per SLOT beside the block pool (the
        architecture says so: mimo-v2's window layers): the batch carries the
        rows' slot ids as ``seq_ids`` beside the block tables."""
        return isinstance(self.layout, BlockKVLayout) and bool(
            getattr(self.arch, "ring_cache_keys", ()) or getattr(self.arch, "slot_cache_keys", ())
        )

    def _layout_input_keys(self):
        if isinstance(self.layout, BlockKVLayout):
            keys = ("slot_mapping", "block_table")
            return keys + ("seq_ids",) if self.per_slot_cache else keys
        if getattr(self.layout, "route_by_seq_id", False):
            return ("seq_ids",)
        return ()

    @property
    def lora_enabled(self) -> bool:
        return self.config.tpu_config.lora_config is not None

    def _block_table_width(self) -> int:
        tc = self.config.tpu_config
        return -(-tc.seq_len // self.layout.block_size)  # ceil div

    def example_batch(self, bucket: int) -> Dict[str, jax.ShapeDtypeStruct]:
        """Shape structs per bucket for AOT lowering (reference:
        model_wrapper.py:205 ``input_generator``)."""
        seq = (
            self.n_active_tokens
            if self.attend_to_cache and not self.prefill_to_cache
            else bucket
        )
        B = self.batch_size
        batch = {
            "input_ids": jax.ShapeDtypeStruct((B, seq), jnp.int32),
            "position_ids": jax.ShapeDtypeStruct((B, seq), jnp.int32),
            "last_token_index": jax.ShapeDtypeStruct((B,), jnp.int32),
            "sampling_params": jax.ShapeDtypeStruct((B, 3), jnp.float32),
        }
        for key in self._layout_input_keys():
            if key == "seq_ids":
                batch[key] = jax.ShapeDtypeStruct((B,), jnp.int32)
            elif key == "slot_mapping":
                batch[key] = jax.ShapeDtypeStruct((B, seq), jnp.int32)
            elif key == "block_table":
                batch[key] = jax.ShapeDtypeStruct((B, self._block_table_width()), jnp.int32)
        if self.lora_enabled:
            batch["adapter_ids"] = jax.ShapeDtypeStruct((B,), jnp.int32)
        for key, (shape, dtype) in self.extra_inputs.items():
            # -1 dims mean "this dispatch's (padded) sequence length" — used
            # by tensor-replacement inputs whose S tracks the bucket
            shape = tuple(seq if d == -1 else d for d in shape)
            batch[key] = jax.ShapeDtypeStruct((B,) + tuple(shape), dtype)
        if self.needs_rng:
            batch["rng"] = jax.ShapeDtypeStruct((2,), jnp.uint32)
        return batch

    def aot_compile(self, params_struct, cache_struct) -> Dict[int, Any]:
        """Lower+compile every bucket ahead of time (reference:
        application_base.py:292 ``compile``). With a persistent compilation
        cache configured, this populates the on-disk artifact."""
        def attach(struct, shardings):
            return jax.tree_util.tree_map(
                lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
                struct, shardings,
            )

        params_struct = attach(params_struct, self._param_shardings)
        cache_struct = attach(cache_struct, self._cache_shardings)
        compiled = {}
        # lower under this app's mesh: constrain()/shard_map kernel dispatch
        # read the ambient abstract mesh at TRACE time — without it the AOT
        # artifact would drop sharding constraints and pallas paths, and the
        # persistent-cache entries would never match the serve-time programs
        with jax.set_mesh(self._mesh):
            for key, prog in self._programs.items():
                compiled[key] = prog.compile(
                    params_struct, cache_struct, self._example_for_key(key)
                )
        return compiled

    def _example_for_key(self, key):
        """Program key -> example batch (multi-step keys are (steps, bucket))."""
        return self.example_batch(key)

    def warmup_batches(self):
        """One dummy host batch per compiled program, so warmup covers the
        whole program grid (application.warmup)."""
        for bucket in self.buckets:
            decode_like = self.attend_to_cache and not self.prefill_to_cache
            seq = self.n_active_tokens if decode_like else bucket
            b = self.batch_size
            yield {
                "input_ids": np.zeros((b, seq), dtype=np.int32),
                "position_ids": np.full(
                    (b, seq), max(bucket - 1 - self.lookahead, 0), dtype=np.int32
                )
                if decode_like
                else np.tile(np.arange(seq, dtype=np.int32), (b, 1)),
                "last_token_index": np.zeros((b,), dtype=np.int32),
                "sampling_params": np.tile([1.0, 1.0, 1.0], (b, 1)).astype(
                    np.float32
                ),
            }

    # ------------------------------------------------------------------
    # dispatch (reference: model_wrapper.py:1314 forward)
    # ------------------------------------------------------------------
    def select_bucket(self, length: int) -> int:
        return autobucketing.get_target_bucket(length, self.buckets, self.bucket_strategy)

    def forward(self, params, cache, batch_np: Dict[str, np.ndarray]):
        """Pad numpy inputs to the target bucket's static shape and dispatch.

        ``batch_np``: input_ids (b, s), position_ids (b, s), last_token_index
        (b,), sampling_params (b, 3). b may be smaller than the compiled batch.
        Returns (outputs, new_cache) with outputs still on device (async).
        ``keep_batch_padding`` (true): per-row outputs keep the compiled
        batch's rows, the first b being the caller's. A slice on the device
        is one small program per b: a caller that walks through every row
        count (the engine's ramp) reads the first b rows on the host instead.
        ``prev_tokens`` + ``prev_rows``: the ``tokens`` output of the previous
        dispatch, left on the device with its batch padding, and per row the
        row of it that holds this row's input id (``-1``: ``input_ids`` does);
        see :func:`decode_next_ids`. Everything else of the batch depends on
        counts, not on token values, and stays host-built.
        """
        tel = self.telemetry
        phase = self._phase
        if tel is not None and tel.enabled:
            _t0 = tel.clock()
        else:
            tel = None
        if faults.ACTIVE_PLAN is not None:
            # failpoint "dispatch.forward": injectable exception / latency
            # for the watchdog + step-recovery machinery. Fires BEFORE any
            # KV write lands, so a retried dispatch replays identically.
            faults.fire(faults.SITE_DISPATCH, self.telemetry)
        with phase("pad"):
            input_ids = np.asarray(batch_np["input_ids"], dtype=np.int32)
            position_ids = np.asarray(batch_np["position_ids"], dtype=np.int32)
            b, s = input_ids.shape

            if self.attend_to_cache and not self.prefill_to_cache:
                if s != self.n_active_tokens:
                    raise ValueError(
                        f"{self.tag}: expected {self.n_active_tokens} active tokens, got {s}"
                    )
                length = int(position_ids.max()) + 1
                # real overflow must still raise loudly in select_bucket; only the
                # speculative lookahead may be clamped to the largest bucket
                # (overshooting writes are dropped and the host discards their tokens)
                if length <= self.buckets[-1]:
                    length = min(length + self.lookahead, self.buckets[-1])
                bucket = self.select_bucket(length)
                pad_s = s
            else:
                bucket = self.select_bucket(s)
                pad_s = bucket

            # pad sequence dim (right padding; pad positions continue arange so
            # their garbage KV lands at future positions that decode overwrites)
            if pad_s > s:
                pad_ids = np.zeros((b, pad_s - s), dtype=np.int32)
                last_pos = position_ids[:, -1:]
                pad_pos = last_pos + np.arange(1, pad_s - s + 1, dtype=np.int32)[None, :]
                input_ids = np.concatenate([input_ids, pad_ids], axis=1)
                position_ids = np.concatenate([position_ids, pad_pos], axis=1)

            last_token_index = np.asarray(
                batch_np.get("last_token_index", np.full((b,), s - 1)), dtype=np.int32
            )
            sampling_params = np.asarray(
                batch_np.get("sampling_params", np.tile([1.0, 1.0, 1.0], (b, 1))),
                dtype=np.float32,
            )
            extra = self._layout_inputs(batch_np, b, s, pad_s, position_ids)
            prev_rows = batch_np.get("prev_rows")
            if prev_rows is not None:  # padded below like every other row input
                extra["prev_rows"] = np.asarray(prev_rows, dtype=np.int32)
            if self.lora_enabled:
                extra["adapter_ids"] = np.asarray(
                    batch_np.get("adapter_ids", np.zeros((b,))), dtype=np.int32
                )
            seq_now = (
                self.n_active_tokens
                if self.attend_to_cache and not self.prefill_to_cache
                else pad_s
            )
            for key, (shape, dtype) in self.extra_inputs.items():
                nd = np.dtype(dtype)
                shape = tuple(seq_now if d == -1 else d for d in shape)
                val = batch_np.get(key)
                if val is None:
                    val = np.zeros((b,) + tuple(shape), dtype=nd)
                else:
                    val = np.asarray(val, dtype=nd)
                    # right-pad any short dim up to the compiled shape (seq dims
                    # grow with the bucket; replacement masks make pads inert)
                    pads = [(0, 0)] + [
                        (0, t - s) for t, s in zip(shape, val.shape[1:])
                    ]
                    if any(p[1] for p in pads):
                        val = np.pad(val, pads)
                extra[key] = np.asarray(val, dtype=nd)

            # pad batch dim (reference: _forward_with_pad model_wrapper.py:569)
            orig_b = b
            if b < self.batch_size:
                input_ids = pad_with_first_batchline(input_ids, self.batch_size)
                position_ids = pad_with_first_batchline(position_ids, self.batch_size)
                last_token_index = pad_with_first_batchline(last_token_index, self.batch_size)
                sampling_params = pad_with_first_batchline(sampling_params, self.batch_size)
                extra = {
                    k: pad_with_first_batchline(v, self.batch_size) for k, v in extra.items()
                }
            elif b > self.batch_size:
                raise ValueError(f"{self.tag}: batch {b} exceeds compiled batch {self.batch_size}")

        with phase("enqueue"):
            device_batch = {
                "input_ids": jnp.asarray(input_ids),
                "position_ids": jnp.asarray(position_ids),
                "last_token_index": jnp.asarray(last_token_index),
                "sampling_params": jnp.asarray(sampling_params),
            }
            device_batch.update({k: jnp.asarray(v) for k, v in extra.items()})
            if prev_rows is not None:
                device_batch["input_ids"] = decode_next_ids(
                    batch_np["prev_tokens"], device_batch.pop("prev_rows"),
                    device_batch["input_ids"],
                )
            if self.needs_rng:
                rng = batch_np.get("rng")
                if rng is None:
                    rng = np.zeros((2,), dtype=np.uint32)
                device_batch["rng"] = jnp.asarray(rng, dtype=jnp.uint32)
            if self.snapshot_hook is not None:
                snap = {
                    "input_ids": input_ids,
                    "position_ids": position_ids,
                    "last_token_index": last_token_index,
                    "sampling_params": sampling_params,
                    **extra,
                }
                self.snapshot_hook(self.tag, snap)
            for hook in self.pre_hooks:
                hook(self.tag)
            # dispatch under this app's mesh: several apps with different meshes
            # can coexist in one process (the reference runs draft+target or
            # encoder+decoder apps side by side the same way)
            with jax.set_mesh(self._mesh):
                outputs, new_cache = self._run_program(bucket, params, cache, device_batch)
            if self.post_hooks or (tel is not None and tel.sync_dispatch):
                # the one place a dispatch waits for the device: the engine
                # step's ``fetch`` phase, wherever it is entered (a phase
                # opened inside another stops the outer one's count)
                with phase("fetch"):
                    jax.block_until_ready(outputs)
                for hook in self.post_hooks:
                    hook(self.tag)
            if tel is not None:
                tel.record_dispatch(
                    self.tag, bucket, self._telemetry_steps(),
                    tel.clock() - _t0,
                    real_tokens=orig_b * s,
                    padded_tokens=self.batch_size * pad_s,
                )
            keep = bool(batch_np.get("keep_batch_padding"))
            if not keep:
                outputs = self._slice_batch_padding(outputs, orig_b)
        if tel is not None and tel.sentinel is not None and "logit_stats" in outputs:
            # numerics sentinel: the compiled-in (B, 5) health readout is
            # recorded AFTER batch-padding rows are sliced away (padding
            # repeats row 0 — double-counting it would skew the series)
            stats = outputs["logit_stats"]
            tel.sentinel.observe(self.tag, bucket, stats[:orig_b] if keep else stats)
        return outputs, new_cache

    def _phase(self, name: str):
        """``Telemetry.phase`` of this wrapper's telemetry: ``pad`` is bucket
        choice and numpy padding up to the first ``jnp.asarray``, ``enqueue``
        the host-to-device puts, the program call and the output slice."""
        tel = self.telemetry
        return _NO_PHASE if tel is None else tel.phase(name)

    def _slice_batch_padding(self, outputs, orig_b: int):
        """Drop batch-padding rows from per-row outputs. The mixed wrapper
        overrides this with a no-op: its compiled batch dim is always 1 (the
        packed token stream) while its outputs lead with the R slot dim.
        Scalars (e.g. the device loop's ``loop_iters``) have no batch dim to
        slice and pass through."""
        return {
            k: (
                v
                if k in ("next_inputs", "captured") or np.ndim(v) == 0
                else v[:orig_b]
            )
            for k, v in outputs.items()
        }

    def _layout_inputs(
        self, batch_np, b: int, s: int, pad_s: int, position_ids
    ) -> Dict[str, np.ndarray]:
        """Layout-specific inputs, padded along the sequence dim.

        Batch-row padding rules keep SPMD lanes harmless: duplicate seq_ids /
        block tables repeat row 0's writes with identical values (idempotent),
        and -1 slots are dropped by the scatter (reference analog: repeated
        first batchline + garbage-slot convention,
        block_kv_cache_manager.py:376 generate_tokengen_slot_mapping)."""
        extra: Dict[str, np.ndarray] = {}
        if getattr(self.layout, "route_by_seq_id", False) or self.per_slot_cache:
            sids = np.asarray(batch_np.get("seq_ids", np.arange(b)), dtype=np.int32)
            tc = self.config.tpu_config
            # bound = the CACHE LINE count (what seq_ids index), not the
            # per-step batch size
            cb = tc.kv_cache_batch_size + tc.kv_cache_padding_size
            if sids.min(initial=0) < 0 or sids.max(initial=0) >= cb:
                # loud host-side gate: an out-of-range seq_id would route a
                # cache write to a clipped line on device (the commit kernel
                # drops it, but a stale-window race with a legit write to the
                # same line is then possible — keep it impossible instead)
                raise ValueError(
                    f"{self.tag}: seq_ids must lie in [0, {cb}); got "
                    f"{sids.tolist()}"
                )
            extra["seq_ids"] = sids
        if isinstance(self.layout, BlockKVLayout):
            bs = self.layout.block_size
            width = self._block_table_width()
            bt = np.asarray(
                batch_np.get("block_table", np.zeros((b, width))), dtype=np.int32
            )
            if bt.shape[1] < width:  # right-pad table with unallocated entries
                bt = np.concatenate(
                    [bt, np.full((b, width - bt.shape[1]), -1, dtype=np.int32)], axis=1
                )
            sm = batch_np.get("slot_mapping")
            if sm is None:
                # derive: token at position p writes slot bt[p//bs]*bs + p%bs
                blk = position_ids // bs
                safe_blk = np.clip(blk, 0, width - 1)
                entry = np.take_along_axis(bt, safe_blk, axis=1)
                sm = np.where(
                    (position_ids >= 0) & (blk < width) & (entry >= 0),
                    entry * bs + position_ids % bs,
                    -1,
                ).astype(np.int32)
            else:
                sm = np.asarray(sm, dtype=np.int32)
                if sm.shape[1] < pad_s:  # seq padding never writes
                    sm = np.concatenate(
                        [sm, np.full((b, pad_s - sm.shape[1]), -1, dtype=np.int32)],
                        axis=1,
                    )
            extra["block_table"] = bt
            extra["slot_mapping"] = sm
        return extra

    def _run_program(self, bucket, params, cache, device_batch):
        """Program lookup + call; the multi-step wrapper keys on (steps,
        bucket) pairs instead."""
        return self._programs[bucket](params, cache, device_batch)

    def iter_programs(self):
        """``(bucket, steps, key, program)`` per compiled-program slot, with
        the key shape normalized (plain wrappers key on the bucket, the
        multi-step wrapper on ``(steps, bucket)``) — what the cost
        observatory (analysis/costs.py) and exporters iterate so they never
        re-learn each wrapper's key convention."""
        for key, prog in self._programs.items():
            bucket, steps = normalize_program_key(key)
            yield bucket, steps, key, prog

    def _telemetry_steps(self) -> int:
        """Decode steps retired per dispatch — the ``steps`` metric label
        (the multi-step wrapper reports its active rung)."""
        return 1

    def forward_device(self, params, cache, device_batch, total_len: int):
        """Hot-path dispatch with inputs already on device (the async loop:
        outputs of step N feed step N+1 without a host round trip; reference:
        async_execution.py:131 execute_model + ranked I/O).

        ``total_len`` (host-tracked) picks the bucket; no device sync happens.
        Telemetry records the host enqueue cost only — this path is never
        synced, even at detail="full", to keep the chain pipelined.
        """
        bucket = self.select_bucket(total_len)
        tel = self.telemetry
        if tel is not None and tel.enabled:
            t0 = tel.clock()
            with tel.phase("enqueue"), jax.set_mesh(self._mesh):
                out = self._run_program(bucket, params, cache, device_batch)
            tel.record_dispatch(
                self.tag, bucket, self._telemetry_steps(), tel.clock() - t0
            )
            return out
        with jax.set_mesh(self._mesh):
            return self._run_program(bucket, params, cache, device_batch)


def _pad_budget_rows(budget, b: int, batch_size: int) -> np.ndarray:
    """Per-row emission budgets padded to the compiled batch with ONES (not
    row 0's value): batch padding duplicates row 0's inputs, and under
    SAMPLED decode a duplicate lane's in-graph chain diverges from row 0
    after its first draw (each batch index gets its own uniform) — a
    1-token budget freezes every padding lane right after its first,
    still-idempotent write, so a diverged lane can never scribble over
    row 0's cache line."""
    if budget is None:
        budget = np.zeros((b,), dtype=np.int32)
    budget = np.asarray(budget, dtype=np.int32)
    if b < batch_size:
        budget = np.concatenate(
            [budget, np.ones((batch_size - b,), dtype=np.int32)]
        )
    return budget


class MultiStepTKGWrapper(ModelWrapper):
    """The ``tkg_multistep`` submodel: one AOT-compiled program per
    (step-rung, KV-bucket) pair running K chained decode steps per dispatch
    (models/base.py ``multi_step_token_gen``).

    The step ladder (autobucketing.multistep_step_ladder) exists for the
    generation tail: a request with 3 tokens left dispatches the 4-step rung,
    not the full-K scan. ``lookahead = max_steps - 1`` widens KV-bucket
    selection so every in-window write position stays inside the compiled
    window (same mechanism as the speculation wrappers).

    Host contract additions over the plain TKG wrapper:
      - ``eos_token_ids`` (B, E<=MULTISTEP_EOS_SLOTS) / ``pad_token_id`` (B,)
        batch inputs drive in-scan EOS masking; both default to inert values
        (-1 / 0) when the host omits them.
      - ``batch_np["decode_steps"]`` (host int) picks the step rung; device
        dispatch passes ``steps=`` explicitly.
    """

    def __init__(self, *args, steps_ladder: Sequence[int], **kwargs):
        super().__init__(*args, **kwargs)
        self.steps_ladder = sorted(steps_ladder)
        self.max_steps = self.steps_ladder[-1]
        # in-window writes reach position + steps - 1
        self.lookahead = self.max_steps - 1
        self.extra_inputs.setdefault(
            "eos_token_ids", ((MULTISTEP_EOS_SLOTS,), np.int32)
        )
        self.extra_inputs.setdefault("pad_token_id", ((), np.int32))
        # per-row in-window emission budget; the zero-fill default means
        # UNLIMITED so warmup / budget-less callers compile the same graph
        self.extra_inputs.setdefault("budget_steps", ((), np.int32))
        self._steps_hint = self.max_steps
        self._steps_building = self.max_steps

    def make_forward(self, bucket: int):
        from nxdi_tpu.models.base import multi_step_token_gen

        return partial(
            multi_step_token_gen,
            self.arch,
            self.inv_freq,
            num_steps=self._steps_building,
            kv_window=bucket,
            policy=self.policy,
            layout=self.layout,
            **self.forward_kwargs,
        )

    def build(self, mesh, param_shardings, cache_shardings) -> None:
        self._mesh = mesh
        self._param_shardings = param_shardings
        self._cache_shardings = cache_shardings
        for steps in self.steps_ladder:
            self._steps_building = steps
            for bucket in self.buckets:
                prog = self._make_program(
                    bucket, mesh, param_shardings, cache_shardings
                )
                prog.label = f"{self.tag}[k{steps},{bucket}]"
                self._programs[(steps, bucket)] = prog
        self._steps_building = self.max_steps

    def _example_for_key(self, key):
        return self.example_batch(key[1])

    def select_steps(self, remaining: Optional[int] = None) -> int:
        if remaining is None:
            return self.max_steps
        return autobucketing.get_target_steps(remaining, self.steps_ladder)

    def forward(self, params, cache, batch_np):
        with self._phase("pad"):
            batch_np = dict(batch_np)
            steps = int(batch_np.pop("decode_steps", self.max_steps))
            if steps not in self.steps_ladder:
                raise ValueError(
                    f"{self.tag}: decode_steps {steps} is not a compiled rung "
                    f"({self.steps_ladder})"
                )
            self._steps_hint = steps
            b = np.asarray(batch_np["input_ids"]).shape[0]
            if "eos_token_ids" not in batch_np:
                batch_np["eos_token_ids"] = np.full(
                    (b, MULTISTEP_EOS_SLOTS), -1, dtype=np.int32
                )
            if "pad_token_id" not in batch_np:
                batch_np["pad_token_id"] = np.zeros((b,), dtype=np.int32)
            batch_np["budget_steps"] = _pad_budget_rows(
                batch_np.get("budget_steps"), b, self.batch_size
            )
        return super().forward(params, cache, batch_np)

    def _run_program(self, bucket, params, cache, device_batch):
        return self._programs[(self._steps_hint, bucket)](
            params, cache, device_batch
        )

    def _telemetry_steps(self) -> int:
        return self._steps_hint

    def forward_device(
        self, params, cache, device_batch, total_len: int,
        steps: Optional[int] = None,
    ):
        self._steps_hint = steps if steps is not None else self.max_steps
        if "budget_steps" not in device_batch:
            # the device-resident window chain has no per-row budgets (the
            # host trims overshoot); zero-fill = UNLIMITED keeps the
            # compiled signature satisfied without changing its semantics
            device_batch = dict(device_batch)
            device_batch["budget_steps"] = jnp.zeros(
                (self.batch_size,), dtype=jnp.int32
            )
        return super().forward_device(params, cache, device_batch, total_len)

    def warmup_batches(self):
        # every (step rung, bucket) pair is its own compiled program — a
        # warmed max-K rung does not cover the tail rungs
        for steps in self.steps_ladder:
            for batch in super().warmup_batches():
                batch["decode_steps"] = steps
                yield batch


class DeviceLoopTKGWrapper(ModelWrapper):
    """The ``tkg_device_loop`` submodel: one AOT-compiled program per
    (cap-rung, KV-bucket) pair running a device-resident decode
    ``while_loop`` with per-row EOS + budget exit
    (models/base.py ``device_loop_token_gen``).

    The cap ladder (autobucketing.device_loop_budget_ladder) sizes the
    STATIC (B, cap) token out-buffer; the loop's trip count is
    data-dependent, so unlike the multistep step ladder a rung bounds —
    never schedules — the work. The dispatcher picks the smallest cap
    covering the LARGEST per-row budget in the batch (the scan ladder had
    to cover the smallest), and the KV bucket covers each row's own last
    write position ``p_i + min(budget_i, cap)`` instead of a uniform
    ``max_len + steps`` — that asymmetry is exactly what lets near-EOS rows
    ride a big launch.

    Host contract additions over the multistep wrapper:
      - ``batch_np["budget_steps"]`` (b,) drives BOTH the in-graph per-row
        halt and the cap/bucket choice; padding lanes are budgeted 1
        (see ``_pad_budget_rows``).
      - ``batch_np["loop_cap"]`` (host int, optional) pins the cap rung —
        warmup uses it to touch every compiled program.
      - outputs carry ``loop_iters`` (scalar int32), the iterations the
        launch actually ran — the host rng schedule advances by it.
      - with ``outfeed_enabled`` every iteration streams ``(t, tokens,
        done)`` into the host out-feed ring (``drain_outfeed``); the result
        buffer is returned either way, so CPU/interpret stays exact.
    """

    def __init__(
        self,
        *args,
        cap_ladder: Sequence[int],
        outfeed_enabled: bool = False,
        **kwargs,
    ):
        super().__init__(*args, **kwargs)
        self.cap_ladder = sorted(cap_ladder)
        self.max_cap = self.cap_ladder[-1]
        self.extra_inputs.setdefault(
            "eos_token_ids", ((MULTISTEP_EOS_SLOTS,), np.int32)
        )
        self.extra_inputs.setdefault("pad_token_id", ((), np.int32))
        self.extra_inputs.setdefault("budget_steps", ((), np.int32))
        self.outfeed_enabled = bool(outfeed_enabled)
        self._outfeed_ring: List[tuple] = []
        self._cap_hint = self.max_cap
        self._cap_building = self.max_cap

    # -- out-feed ring ---------------------------------------------------
    def _outfeed_tap(self, t, tokens, done) -> None:
        # called from XLA via an UNORDERED io_callback: entries may arrive
        # out of iteration order; each carries its own index t
        self._outfeed_ring.append(
            (int(t), np.asarray(tokens).copy(), np.asarray(done).copy())
        )

    def drain_outfeed(self) -> List[tuple]:
        """All ``(t, tokens, done)`` entries of the LAST launch, iteration
        order restored. Flushes pending callbacks first (the unordered
        io_callback only promises delivery by the effects barrier)."""
        jax.effects_barrier()
        ring, self._outfeed_ring = self._outfeed_ring, []
        return sorted(ring, key=lambda e: e[0])

    # -- build: one program per (cap, bucket) ----------------------------
    def make_forward(self, bucket: int):
        from nxdi_tpu.models.base import device_loop_token_gen

        return partial(
            device_loop_token_gen,
            self.arch,
            self.inv_freq,
            max_steps=self._cap_building,
            kv_window=bucket,
            policy=self.policy,
            layout=self.layout,
            outfeed=self._outfeed_tap if self.outfeed_enabled else None,
            **self.forward_kwargs,
        )

    def build(self, mesh, param_shardings, cache_shardings) -> None:
        self._mesh = mesh
        self._param_shardings = param_shardings
        self._cache_shardings = cache_shardings
        for cap in self.cap_ladder:
            self._cap_building = cap
            for bucket in self.buckets:
                prog = self._make_program(
                    bucket, mesh, param_shardings, cache_shardings
                )
                prog.label = f"{self.tag}[cap{cap},{bucket}]"
                self._programs[(cap, bucket)] = prog
        self._cap_building = self.max_cap

    def _example_for_key(self, key):
        return self.example_batch(key[1])

    def select_cap(self, max_budget: int) -> int:
        return autobucketing.get_target_steps(max_budget, self.cap_ladder)

    def forward(self, params, cache, batch_np):
        with self._phase("pad"):
            batch_np = dict(batch_np)
            b = np.asarray(batch_np["input_ids"]).shape[0]
            if "eos_token_ids" not in batch_np:
                batch_np["eos_token_ids"] = np.full(
                    (b, MULTISTEP_EOS_SLOTS), -1, dtype=np.int32
                )
            if "pad_token_id" not in batch_np:
                batch_np["pad_token_id"] = np.zeros((b,), dtype=np.int32)
            real_budget = np.asarray(
                batch_np.get("budget_steps", np.zeros((b,), np.int32)),
                dtype=np.int32,
            )
            cap = batch_np.pop("loop_cap", None)
            if cap is None:
                # smallest rung covering the largest per-row ask; an unlimited
                # (<= 0) budget asks for the full ladder
                max_ask = (
                    int(real_budget.max(initial=0))
                    if (real_budget > 0).all() and real_budget.size
                    else self.max_cap
                )
                cap = self.select_cap(max_ask)
            cap = int(cap)
            if cap not in self.cap_ladder:
                raise ValueError(
                    f"{self.tag}: loop_cap {cap} is not a compiled rung "
                    f"({self.cap_ladder})"
                )
            self._cap_hint = cap
            batch_np["budget_steps"] = _pad_budget_rows(
                real_budget, b, self.batch_size
            )
            # per-row last write position p_i + min(budget_i, cap) sizes the KV
            # bucket; the base forward adds `lookahead` to pos.max()+1, so feed
            # it the gap between that and the loop's true reach
            pos = np.asarray(batch_np["position_ids"], dtype=np.int32)
            p_last = pos.max(axis=1)  # (b,)
            m = np.where(real_budget > 0, np.minimum(real_budget, cap), cap)
            needed = int((p_last + m).max()) if b else cap
            self.lookahead = max(needed - (int(pos.max()) + 1), 0)
            self._outfeed_ring.clear()
        return super().forward(params, cache, batch_np)

    def _run_program(self, bucket, params, cache, device_batch):
        return self._programs[(self._cap_hint, bucket)](
            params, cache, device_batch
        )

    def _telemetry_steps(self) -> int:
        return self._cap_hint

    def warmup_batches(self):
        # every (cap rung, bucket) pair is its own compiled program; a
        # 1-token budget makes the warmed loop exit after one iteration —
        # warmup pays compilation, not max_cap decode steps
        for cap in self.cap_ladder:
            for batch in super().warmup_batches():
                batch["loop_cap"] = cap
                b = batch["input_ids"].shape[0]
                batch["budget_steps"] = np.ones((b,), dtype=np.int32)
                yield batch


class MixedModelWrapper(ModelWrapper):
    """The ``mixed_model`` submodel: ONE program serving a whole mixed
    prefill+decode serving step (ops/kernels/ragged_paged_attention).

    Shape contract (R = scheduler slots = tkg_batch_size, T = token bucket):
      - input_ids / position_ids (1, T): the flat packed token stream —
        prefill chunks and decode singles concatenated, -1-row padded tail
      - ``mixed_row_ids`` (1, T) int32: per-token slot index, -1 = padding
      - ``slot_mapping`` (1, T): per-token KV pool slot, HOST-computed per
        row (the generic position-derived path indexes the COMBINED table
        and is wrong here — forward() refuses to derive)
      - ``block_table`` (1, R*Wt): R per-row tables concatenated; idle
        slots all -1
      - ``last_token_index`` (R,): packed index of each row's newest token
      - ``sampling_params`` (R, 3); outputs["tokens"] (R, 1)

    Buckets count TOTAL packed tokens (autobucketing.mixed_token_buckets),
    not KV windows — ``window_buckets = False`` keeps them out of
    ``decode_window_limit``.
    """

    window_buckets = False

    def __init__(self, *args, num_rows: int, **kwargs):
        super().__init__(*args, **kwargs)
        self.num_rows = num_rows
        self.extra_inputs.setdefault("mixed_row_ids", ((-1,), np.int32))

    def example_batch(self, bucket: int):
        batch = super().example_batch(bucket)
        R = self.num_rows
        batch["last_token_index"] = jax.ShapeDtypeStruct((R,), jnp.int32)
        batch["sampling_params"] = jax.ShapeDtypeStruct((R, 3), jnp.float32)
        batch["block_table"] = jax.ShapeDtypeStruct(
            (1, R * self._block_table_width()), jnp.int32
        )
        return batch

    def forward(self, params, cache, batch_np):
        with self._phase("pad"):
            batch_np = dict(batch_np)
            if "slot_mapping" not in batch_np:
                # the base derive path maps position -> combined-table entry,
                # which aliases every row onto row 0's pages — never legal here
                raise ValueError(
                    f"{self.tag}: mixed dispatch requires a host-computed "
                    "slot_mapping (per-token, through each row's own table)"
                )
            s = int(np.asarray(batch_np["input_ids"]).shape[1])
            bucket = self.select_bucket(s)
            # pre-pad the row tags with -1 BEFORE the generic extra-input pad:
            # np.pad's zero fill would tag padding tokens as row 0
            rids = np.asarray(batch_np["mixed_row_ids"], dtype=np.int32)
            if rids.ndim == 1:
                rids = rids[None, :]
            if rids.shape[1] < bucket:
                rids = np.concatenate(
                    [rids, np.full((rids.shape[0], bucket - rids.shape[1]), -1, np.int32)],
                    axis=1,
                )
            batch_np["mixed_row_ids"] = rids
        out = super().forward(params, cache, batch_np)
        tel = self.telemetry
        if tel is not None and tel.enabled:
            tel.record_mixed(bucket, packed_tokens=s, padded_tokens=bucket)
        return out

    def _slice_batch_padding(self, outputs, orig_b: int):
        # the packed batch dim is always exactly 1; outputs lead with the R
        # slot dim (tokens (R, 1), logit_stats (R, 5)) — never slice them
        return outputs

    def warmup_batches(self):
        R = self.num_rows
        wt = self._block_table_width()
        for bucket in self.buckets:
            # all -1: no KV writes, all-masked attention (finite — NEG_INF
            # is a large negative constant, so fully-masked rows softmax to
            # uniform garbage the last-token gather never reads)
            yield {
                "input_ids": np.zeros((1, bucket), dtype=np.int32),
                "position_ids": np.tile(np.arange(bucket, dtype=np.int32), (1, 1)),
                "last_token_index": np.zeros((R,), dtype=np.int32),
                "sampling_params": np.tile([1.0, 1.0, 1.0], (R, 1)).astype(np.float32),
                "mixed_row_ids": np.full((1, bucket), -1, dtype=np.int32),
                "slot_mapping": np.full((1, bucket), -1, dtype=np.int32),
                "block_table": np.full((1, R * wt), -1, dtype=np.int32),
            }
