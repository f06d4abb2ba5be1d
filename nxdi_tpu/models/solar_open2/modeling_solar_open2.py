"""Solar-Open2: a decoder that mixes Kimi Delta Attention layers (KDA: a gated
delta rule with a decay a key channel; Kimi Linear, arXiv:2510.26692) and gated
NoPE GQA layers (``gqa_layers``: one in four), with routed experts and one
shared expert in every layer (``first_k_dense_replace: 0``).

Served on the paged path only. The cache tree has three kinds of leaf:

- ``k`` / ``v``: the block pool of the GQA layers, by block table (the engine's
  ``BlockSpaceManager``, unchanged);
- ``kda_state``: the KDA layers' float32 state ``(L, slots + 1, H, D, D)`` by
  slot id;
- ``kda_conv``: the KDA layers' convolution windows ``(L, slots + 1, K - 1, 3,
  H x D)``, the last ``K - 1`` q, k and v projections of a slot.

The last slot of both stores is spare: a batch's padding rows land there (the
in-place update is not idempotent). A GQA layer runs ``models/base.py``'s layer
scan as it is (paged decode and flash prefill kernels, the output gate after the
core); a KDA layer has no attention call: a prefill runs the chunked form and
leaves the state and the window at the prompt's last token, a decode step
updates the slot's state in place (ops/delta_rule.py).

A KDA layer, with ``x`` the normed hidden state, ``H`` heads of ``d``:

    q, k, v = SiLU(conv(W_{q,k,v} x));  q, k L2-normed a head, q x d^-1/2
    g = -exp(A_log) softplus(W_fb W_fa x + dt_bias)      (a head and KEY channel)
    beta = 2 sigmoid(W_b x)                               (kda_allow_neg_eigval)
    o = RMSNorm_head(S^T q) w sigmoid(W_gb W_ga x);   out = W_o o

The experts: sigmoid scores over all ``n_routed_experts_total``, the top k of
scores + a selection bias, weights from the scores renormalised; one chip's
SHARE of an expert-parallel deployment holds ``n_routed_experts`` of them from
``first_routed_expert`` on (ops/moe.py ``MoEArch.held_experts``), beside the
shared expert, which every chip computes.

HF weight layout (assumed from Kimi Linear's and the DeepSeek lineage's):
``self_attn.{q,k,v,o}_proj``, ``self_attn.gate_proj`` (GQA's elementwise gate),
``self_attn.{q,k,v}_conv1d``, ``self_attn.{f,g}_{a,b}_proj``, ``self_attn.b_proj``,
``self_attn.A_log``, ``self_attn.dt_bias``, ``self_attn.o_norm``; ``mlp.gate``
(+ ``e_score_correction_bias``), ``mlp.experts.{i}.*``, ``mlp.shared_experts.*``.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from nxdi_tpu.analysis.costs import resolve_chip
from nxdi_tpu.config import InferenceConfig
from nxdi_tpu.models import dense
from nxdi_tpu.models.base import DecoderArch, kind_schedule
from nxdi_tpu.ops.moe import MoEArch, moe_parallel_fields, moe_shape_struct

GQA, KDA = "gqa", "kda"


class SolarOpen2InferenceConfig(dense.DenseInferenceConfig):
    REQUIRED = [
        "hidden_size", "num_hidden_layers", "num_attention_heads", "num_key_value_heads",
        "head_dim", "vocab_size", "intermediate_size", "rms_norm_eps", "gqa_layers",
        "linear_attn_config", "n_routed_experts", "num_experts_per_tok", "moe_intermediate_size",
    ]

    #: the published switches this family's layers are written for
    SWITCHES = dict(use_rope=False, use_gqa_gate=True, kda_allow_neg_eigval=True,
                    kda_use_full_proj=False, first_k_dense_replace=0)

    def add_derived_config(self):
        super().add_derived_config()
        for key, want in self.SWITCHES.items():
            if getattr(self, key, want) != want:
                raise NotImplementedError(f"solar_open2 is written for {key} = {want!r}")
        lin = self.linear_attn_config
        if lin.get("num_kv_heads") not in (None, lin["num_heads"]):
            raise NotImplementedError("solar_open2: grouped KDA heads")


@dataclass(frozen=True)
class SolarOpen2Arch:
    """The two kinds of layer and the depth-ordered walk
    (``models/base.py kind_schedule``)."""

    gqa: DecoderArch
    kda: DecoderArch  # heads, head width, norms and experts of the KDA layers
    schedule: Tuple[Tuple[str, int, int, int], ...]
    conv_kernel: int
    gate_rank: int

    #: stores held per SLOT beside the pool (runtime/model_wrapper.py
    #: ``per_slot_cache``), taken row-major as declared in every program, in the
    #: TPU tiling named here (``_pinned_cache_layouts``): a window row's q, k and
    #: v (3, H x D) in bf16 tiles of four (what the compiler takes for three),
    #: the state's (D, D) in (8, 128) tiles
    slot_cache_keys = {"kda_conv": ((4, 128), (2, 1)), "kda_state": ((8, 128),)}
    #: the axis of a store that counts its rows a slot (serving/engine.py ``_kv_held``)
    slot_rows_axis = 2

    def kv_cache_spec(self, batch_size, max_len, quant_dtype=None):
        return self.gqa.kv_cache_spec(batch_size, max_len, quant_dtype=quant_dtype)

    @property
    def num_layers(self):
        return self.gqa.num_layers + self.kda.num_layers

    def __getattr__(self, name):
        # the runtime reads generic decoder attrs (vocab, dtype, sampler wiring)
        return getattr(object.__getattribute__(self, "gqa"), name)


def held_kinds(config) -> list:
    gqa = set(config.gqa_layers)
    return [GQA if i in gqa else KDA for i in range(config.num_hidden_layers)]


def _moe_arch(config: InferenceConfig) -> MoEArch:
    # a share: ``n_routed_experts`` counts the experts held here, the router
    # keeps the published width (models/mimo_v2 reads the same keys)
    held = config.n_routed_experts
    E = getattr(config, "n_routed_experts_total", None) or held
    shared = int(getattr(config, "n_shared_experts", 0) or 0)
    return MoEArch(
        num_experts=E,
        top_k=config.num_experts_per_tok,
        intermediate_size=config.moe_intermediate_size,
        hidden_act=getattr(config, "hidden_act", "silu"),
        norm_topk_prob=bool(getattr(config, "norm_topk_prob", True)),
        sigmoid_routing=True,
        routed_scaling=float(getattr(config, "routed_scaling_factor", None) or 1.0),
        correction_bias=True,
        shared_expert_intermediate_size=shared * config.moe_intermediate_size or None,
        held_experts=held if held != E else None,
        first_held=int(getattr(config, "first_routed_expert", 0) or 0),
        ridge_rows=resolve_chip(config.tpu_config).ridge_rows if held != E else None,
        **moe_parallel_fields(config.tpu_config, E),
    )


def build_arch(config: InferenceConfig, **overrides) -> SolarOpen2Arch:
    if config.tpu_config.tp_degree != 1:
        raise NotImplementedError("solar_open2 does not support tensor parallel yet")
    kinds = held_kinds(config)
    moe = _moe_arch(config)
    lin = config.linear_attn_config
    gqa = dense.build_arch(
        config, num_layers=kinds.count(GQA), no_rope=True, attn_out_gate=True,
        attention_scale=float(config.head_dim) ** -0.5, moe=moe, **overrides,
    )
    kda = dense.build_arch(
        config, num_layers=kinds.count(KDA), num_attention_heads=lin["num_heads"],
        num_kv_heads=lin["num_heads"], head_dim=lin["head_dim"], moe=moe, **overrides,
    )
    return SolarOpen2Arch(
        gqa=gqa, kda=kda, schedule=kind_schedule(kinds),
        conv_kernel=int(lin["short_conv_kernel_size"]), gate_rank=lin["head_dim"],
    )


def build_inv_freq(config: InferenceConfig) -> np.ndarray:
    return np.zeros((1,), np.float32)  # no rope anywhere (use_rope: false)


# ---------------------------------------------------------------------------
# The KDA layer
# ---------------------------------------------------------------------------


def _kda_mixer(arch: SolarOpen2Arch, p, x, conv, state, li, ci, attend_to_cache):
    """One KDA layer's mixer: ``(out (B, S, hidden), conv, state)``."""
    from nxdi_tpu.models.base import _linear
    from nxdi_tpu.ops import delta_rule
    from nxdi_tpu.ops.linear_attention import spare_slots, write_states
    from nxdi_tpu.ops.norms import rms_norm

    t = arch.kda
    B, S, _ = x.shape
    H, D = t.num_attention_heads, t.head_dim
    f32 = jnp.float32

    def low_rank(a, b):  # float32 out of the second product: what follows is a gate
        return jnp.dot(jnp.dot(x, p[a]["w"]), p[b]["w"], preferred_element_type=f32)

    with jax.named_scope("attn.qkv"):
        qkv = jnp.stack([_linear(x, p[f"{n}_proj"]) for n in "qkv"], axis=2)  # (B, S, 3, H x D)
    with jax.named_scope("kda.gates"):
        f = (low_rank("f_a", "f_b") + p["dt_bias"].astype(f32)).reshape(B, S, H, D)
        g = -jnp.exp(p["A_log"].astype(f32))[:, None] * jax.nn.softplus(f)
        beta = 2.0 * jax.nn.sigmoid(jnp.dot(x, p["b_proj"]["w"], preferred_element_type=f32))
        gate = jax.nn.sigmoid(low_rank("g_a", "g_b"))
    slots = spare_slots(ci["seq_ids"].astype(jnp.int32), state.shape[1])
    with jax.named_scope("kda.conv"):
        if attend_to_cache:
            if S != 1:
                raise NotImplementedError("solar_open2: a cache-attending prefill of the state")
            y, window = delta_rule.conv_step(conv[li, slots], qkv[:, 0], p["conv"])
            conv = conv.at[li, slots].set(window)
            y = y[:, None]
        else:
            last = ci.get("last_token_index")
            last = jnp.full((B,), S - 1, jnp.int32) if last is None else last.astype(jnp.int32)
            y, window = delta_rule.conv_prefill(qkv, p["conv"], last)
            conv = write_states(conv, li, slots, window)
            # behind the last real token the recurrence stands still
            real = (jnp.arange(S)[None, :] <= last[:, None])[..., None]
            g, beta = jnp.where(real[..., None], g, 0.0), jnp.where(real, beta, 0.0)
        y = jax.nn.silu(y).reshape(B, S, 3, H, D)
        q = delta_rule.l2_normed(y[:, :, 0]) * D ** -0.5
        k, v = delta_rule.l2_normed(y[:, :, 1]), y[:, :, 2]
    if attend_to_cache:
        o, state = delta_rule.decode_step(state, li, slots, q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0])
        o = o[:, None]
    else:
        o, last_state = delta_rule.chunked_prefill(q, k, v, g, beta)
        with jax.named_scope("kda.state"):
            state = write_states(state, li, slots, last_state)
    with jax.named_scope("kda.out"):
        # a norm a HEAD (FLA's FusedRMSNormGated(head_dim)), then the gate
        o = rms_norm(o, p["o_norm"], t.rms_norm_eps).reshape(B, S, H * D) * gate
        out = _linear(o.astype(x.dtype), p["o_proj"])
    return out, conv, state


def _kda_segment(arch, seg, hidden, conv, state, lo, hi, ci, attend_to_cache, policy, held_tally):
    """A run of KDA layers as one scan, both stores WHOLE as its carry, written
    at ``(layer, slot)`` in place; the experts as ``models/base.py``'s layer
    scan runs them (stacked out of the xs where the form is not dense)."""
    from nxdi_tpu.models.base import _extract_stacked_weights, _norm, constrain
    from nxdi_tpu.ops import moe as moe_ops

    t = arch.kda
    B, S = hidden.shape[:2]
    seg, _, _, moe_st = _extract_stacked_weights(t, seg, B * S)
    # a FRESH prefill's rows behind a prompt's last token are bucket padding
    last_real = None if attend_to_cache else ci.get("last_token_index")
    count = held_tally is not None
    # ([held pairs, routed layers], slot rows where the expert layers are compact), as
    # run_decoder_layers tallies them
    tallied = (jnp.zeros((2,), jnp.int32),
               jnp.int32(0) if moe_ops.expert_form(t.moe, B * S) == "compact" else None)

    def body(carry, xs):
        (h, conv_, state_), (pair, slot_rows) = carry
        lp, li = xs
        with jax.named_scope("attn.block"):
            x = _norm(t, h, lp["input_layernorm"])
        mixed, conv_, state_ = _kda_mixer(arch, lp["attn"], x, conv_, state_, li, ci, attend_to_cache)
        tally = [] if count else None
        stacked = None if moe_st is None else (*moe_st, li - lo)
        with jax.named_scope("ffn.block"):
            h = h + mixed
            ff = moe_ops.moe_block(t, t.moe, lp["moe"], _norm(t, h, lp["post_attention_layernorm"]),
                                   policy.hidden, tally, stacked, last_real)
            h = constrain(h + ff, policy.hidden)
        if count:
            pair = pair + jnp.stack([sum((n for n, _ in tally), jnp.int32(0)), jnp.int32(len(tally))])
            if slot_rows is not None:
                slot_rows = sum((r for _, r in tally), slot_rows)
        return ((h, conv_, state_), (pair, slot_rows)), None

    idx = jnp.arange(lo, hi, dtype=jnp.int32)
    ((hidden, conv, state), tallied), _ = jax.lax.scan(body, ((hidden, conv, state), tallied), (seg, idx))
    if count:
        held_tally.append(tallied)
    return hidden, conv, state


def _walk(arch: SolarOpen2Arch, params, hidden, cache, position_ids, attend_to_cache, policy,
          layout, ci, held_tally):
    """The segments in depth order, each kind's cache WHOLE into every segment."""
    from nxdi_tpu.kvcache.kv_cache import BlockKVCacheSpec
    from nxdi_tpu.models.base import run_decoder_layers

    pool = {"k": cache["k"], "v": cache["v"]}
    conv, state = cache["kda_conv"], cache["kda_state"]
    g = arch.gqa
    pool_spec = BlockKVCacheSpec(
        num_layers=g.num_layers, num_blocks=pool["k"].shape[1] // layout.block_size,
        block_size=layout.block_size, num_kv_heads=g.num_kv_heads, head_dim=g.head_dim, dtype=g.dtype,
    )
    for kind, lo, hi, seg_idx in arch.schedule:
        seg = params["segments"][seg_idx]
        if kind == GQA:
            with jax.named_scope("layers.full"):
                hidden, pool = run_decoder_layers(
                    g, seg, hidden, None, None, pool, position_ids, pool_spec, attend_to_cache,
                    policy=policy, layout=layout, cache_inputs=ci, moe_held_tally=held_tally,
                    first_layer=lo,
                )
                # a short segment is no loop the compiler keeps: without this it
                # may recompute the write on the pool it was handed and hold two
                hidden, pool = jax.lax.optimization_barrier((hidden, pool))
        else:
            with jax.named_scope("layers.kda"):
                hidden, conv, state = _kda_segment(
                    arch, seg, hidden, conv, state, lo, hi, ci, attend_to_cache, policy, held_tally)
                hidden, conv, state = jax.lax.optimization_barrier((hidden, conv, state))
    return hidden, {"k": pool["k"], "v": pool["v"], "kda_conv": conv, "kda_state": state}


def causal_lm_forward(
    arch: SolarOpen2Arch,
    inv_freq,
    params: Dict[str, Any],
    cache: Dict[str, jax.Array],
    batch: Dict[str, jax.Array],
    *,
    attend_to_cache: bool,
    kv_window=None,
    policy=None,
    layout=None,
    gather_last_token: bool = True,
    output_logits: bool = False,
    output_all_logits: bool = False,
    on_device_sampling: bool = True,
    do_sample: bool = False,
    global_topk: int = 256,
    deterministic: bool = False,
    **_unused,
):
    from nxdi_tpu.config import to_jax_dtype
    from nxdi_tpu.kvcache.kv_cache import BlockKVLayout
    from nxdi_tpu.models.base import (
        collect_cache_inputs, constrain, counts_held_pairs, held_pair_outputs,
    )
    from nxdi_tpu.ops import sampling as sampling_ops
    from nxdi_tpu.ops.norms import rms_norm
    from nxdi_tpu.parallel.policy import DEFAULT_POLICY

    policy = policy or DEFAULT_POLICY
    if not isinstance(layout, BlockKVLayout):
        raise NotImplementedError("solar_open2 is served over the block KV layout only")
    ci = collect_cache_inputs(batch)
    if "seq_ids" not in ci or "slot_mapping" not in ci:
        raise ValueError(
            "the state and the windows are addressed by the rows' slot ids: the batch needs "
            "seq_ids beside block_table / slot_mapping"
        )
    t = arch.gqa
    input_ids, position_ids = batch["input_ids"], batch["position_ids"]
    B = input_ids.shape[0]

    with jax.named_scope("embed"):
        hidden = jnp.take(params["embed_tokens"], input_ids, axis=0).astype(to_jax_dtype(t.dtype))
        hidden = constrain(hidden, policy.hidden)
    held_tally = [] if counts_held_pairs(t.moe, input_ids.shape, attend_to_cache, layout) else None
    hidden, new_cache = _walk(
        arch, params, hidden, cache, position_ids, attend_to_cache, policy, layout, ci, held_tally)

    with jax.named_scope("final_norm"):
        hidden = rms_norm(hidden, params["norm"], t.rms_norm_eps)
    with jax.named_scope("lm_head"):
        if gather_last_token and not output_all_logits:
            idx = batch["last_token_index"][:, None, None]
            hidden = jnp.take_along_axis(hidden, jnp.broadcast_to(idx, (B, 1, hidden.shape[2])), axis=1)
        logits = (hidden @ params["lm_head"].astype(hidden.dtype)).astype(jnp.float32)
        logits = constrain(logits, policy.logits)
        logits = sampling_ops.mask_padded_logits(logits, t.vocab_pad)
    if output_all_logits and gather_last_token:
        idx = batch["last_token_index"][:, None, None]
        last_logits = jnp.take_along_axis(logits, jnp.broadcast_to(idx, (B, 1, logits.shape[2])), axis=1)
    else:
        last_logits = logits

    outputs: Dict[str, jax.Array] = {}
    if held_tally:
        # two scalars beside the tokens (serving/engine.py _collect_decode)
        outputs.update(held_pair_outputs(held_tally, prefill=input_ids.shape[1] > 1))
    if on_device_sampling:
        with jax.named_scope("sample"):
            outputs["tokens"] = sampling_ops.sample(
                last_logits[:, -1, :], batch["sampling_params"], rng=batch.get("rng"),
                do_sample=do_sample, global_topk=global_topk, deterministic=deterministic,
            )[:, None]
    if output_logits or output_all_logits or not on_device_sampling:
        outputs["logits"] = logits[..., : t.vocab_size - t.vocab_pad]
    return outputs, new_cache


# ---------------------------------------------------------------------------
# Conversion / specs / structs
# ---------------------------------------------------------------------------


def _segments(config):
    """[(kind, n layers)] of the stacked params segments, in depth order."""
    return [(kind, hi - lo) for kind, lo, hi, _ in kind_schedule(held_kinds(config))]


def _layer_struct(arch: SolarOpen2Arch, kind: str, n: int, dt):
    def leaf(*shape, dtype=dt):
        return jax.ShapeDtypeStruct((n,) + shape, dtype)

    t = arch.gqa if kind == GQA else arch.kda
    H, NH, NKV, D = t.hidden_size, t.num_attention_heads, t.num_kv_heads, t.head_dim
    attn = {"q_proj": {"w": leaf(H, NH * D)}, "k_proj": {"w": leaf(H, NKV * D)},
            "v_proj": {"w": leaf(H, NKV * D)}, "o_proj": {"w": leaf(NH * D, H)}}
    if kind == GQA:
        attn["gate_proj"] = {"w": leaf(H, NH * D)}  # elementwise, heads x head_dim wide
    else:
        R, HD = arch.gate_rank, NH * D
        attn.update(
            conv=leaf(3, arch.conv_kernel, HD),  # q, k, v: a depthwise kernel a channel
            f_a={"w": leaf(H, R)}, f_b={"w": leaf(R, HD)}, g_a={"w": leaf(H, R)}, g_b={"w": leaf(R, HD)},
            b_proj={"w": leaf(H, NH)}, A_log=leaf(NH, dtype=jnp.float32),
            dt_bias=leaf(HD, dtype=jnp.float32), o_norm=leaf(D),
        )
    return {"input_layernorm": leaf(H), "post_attention_layernorm": leaf(H), "attn": attn,
            "moe": moe_shape_struct(t.moe, H, n, dt)}


def param_shape_struct(config: InferenceConfig):
    arch = build_arch(config)
    dt = dense.np_dtype(arch.gqa.dtype)
    H, V = arch.gqa.hidden_size, arch.gqa.vocab_size
    leaf = lambda *shape: jax.ShapeDtypeStruct(shape, dt)  # noqa: E731
    return {
        "embed_tokens": leaf(V, H),
        "segments": [_layer_struct(arch, kind, n, dt) for kind, n in _segments(config)],
        "norm": leaf(H),
        "lm_head": leaf(H, V),
    }


def param_specs(config: InferenceConfig):
    from jax.sharding import PartitionSpec as P

    # tensor parallel is refused (build_arch): every leaf whole on its chip
    return jax.tree_util.tree_map(lambda s: P(*((None,) * len(s.shape))), param_shape_struct(config))


def convert_hf_state_dict(state_dict: Dict[str, np.ndarray], config: InferenceConfig):
    from nxdi_tpu.ops.moe import convert_hf_experts

    arch = build_arch(config)
    dt = dense.np_dtype(arch.gqa.dtype)
    moe = arch.gqa.moe

    def cast(x, dtype=dt):
        return np.asarray(x, dtype)

    def layer(i, kind):
        pre = f"model.layers.{i}."

        def get(name):
            for key in (pre + name, pre.replace("model.", "", 1) + name):
                if key in state_dict:
                    return np.asarray(state_dict[key])
            raise KeyError(pre + name)

        attn = {f"{p}_proj": {"w": cast(get(f"self_attn.{p}_proj.weight").T)} for p in "qkvo"}
        if kind == GQA:
            attn["gate_proj"] = {"w": cast(get("self_attn.gate_proj.weight").T)}
        else:
            # conv1d weights (channels, 1, K) -> (K, channels), q k v stacked
            attn["conv"] = cast(np.stack([get(f"self_attn.{p}_conv1d.weight")[:, 0, :].T for p in "qkv"]))
            for p in ("f_a", "f_b", "g_a", "g_b", "b"):
                attn[p if p != "b" else "b_proj"] = {"w": cast(get(f"self_attn.{p}_proj.weight").T)}
            attn["A_log"] = cast(get("self_attn.A_log").reshape(-1), np.float32)
            attn["dt_bias"] = cast(get("self_attn.dt_bias").reshape(-1), np.float32)
            attn["o_norm"] = cast(get("self_attn.o_norm.weight"))
        first = moe.first_held  # a share converts its own experts alone
        routed = convert_hf_experts(
            get, cast, moe.experts_here, "mlp.gate.weight",
            lambda j, proj: f"mlp.experts.{first + j}.{proj}_proj.weight")
        routed["router"]["e_bias"] = cast(get("mlp.gate.e_score_correction_bias"), np.float32)
        routed["shared_expert"] = {
            f"{p}_proj": {"w": cast(get(f"mlp.shared_experts.{p}_proj.weight").T)} for p in ("gate", "up", "down")}
        return {
            "input_layernorm": cast(get("input_layernorm.weight")),
            "post_attention_layernorm": cast(get("post_attention_layernorm.weight")),
            "attn": attn, "moe": routed,
        }

    segments, i = [], 0
    for kind, n in _segments(config):
        segments.append(dense.tree_stack([layer(i + j, kind) for j in range(n)]))
        i += n

    def top(name):
        for key in (f"model.{name}", name):
            if key in state_dict:
                return state_dict[key]
        raise KeyError(name)

    return {
        "embed_tokens": cast(top("embed_tokens.weight")),
        "segments": segments,
        "norm": cast(top("norm.weight")),
        "lm_head": cast(np.asarray(state_dict["lm_head.weight"]).T),
    }


class SolarOpen2ForCausalLM:
    def __new__(cls, *args, **kwargs):
        from nxdi_tpu.models.solar_open2.application import SolarOpen2Application

        return SolarOpen2Application(*args, **kwargs)


def __getattr__(name):
    # lazy APPLICATION_CLS: application.py imports this module
    if name == "APPLICATION_CLS":
        from nxdi_tpu.models.solar_open2.application import SolarOpen2Application

        return SolarOpen2Application
    raise AttributeError(name)
