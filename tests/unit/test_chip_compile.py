"""AOT compiles of the main path's Pallas kernels for a DESCRIBED v5e chip.

No chip is attached here: the TPU compiler compiles for ``v5e:2x2`` as it is
described (``jax.experimental.topologies``), which is what refuses a kernel
the interpreter accepts — a slice off the tiling, too much fast memory.
Llama-3.2-1B (D=64, 32/8 heads, H=2048, I=8192) and Llama-3.1-8B (D=128,
32/8 heads, H=4096, I=14336) shapes, one launch each, ~2 s a compile.

A compile that passes is a compile, never a run: numerics of the compiled
kernels live in tests/tpu/ and run on the chip.

The topology is described inside a fixture (never at import: one process at
a time may load the TPU's library, and every xdist worker imports this file),
kernels are steered off the interpreter by monkeypatch, and the persistent
compilation cache is off around the compiles (an entry written for a
described chip cannot be read back without one).
"""

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from nxdi_tpu.ops.kernels import flash_attention as fa
from nxdi_tpu.ops.kernels import fused_proj as fp
from nxdi_tpu.ops.kernels import kv_commit, mode
from nxdi_tpu.ops.kernels.ragged_paged_attention import (
    ragged_paged_attention,
    ragged_paged_kernel_supported,
)

BF16 = jnp.bfloat16
I32 = jnp.int32

# (head_dim, q heads, kv heads, hidden, intermediate)
WIDTHS = {
    "1b": dict(D=64, H=32, KV=8, hidden=2048, inter=8192),
    "8b": dict(D=128, H=32, KV=8, hidden=4096, inter=14336),
}
BLOCK = 128  # pa_block_size of the serving stack
SEQ = 2048  # decode window
PROMPT = 1024  # prompt bucket
SLOTS = 8  # decode batch rows


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_compile_cache():
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture
def mosaic(monkeypatch, no_compile_cache):
    """Kernels lower through Mosaic, not the interpreter."""
    monkeypatch.setattr(mode, "interpret", lambda: False)


def _compile(fn, one_chip, *shapes):
    """Compile ``fn`` for the described chip; returns the optimized HLO."""
    args = [
        jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
        for shape, dtype in shapes
    ]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text, "no Mosaic kernel in the compiled program"
    return text


WIDTH_IDS = sorted(WIDTHS)


@pytest.mark.parametrize("width", WIDTH_IDS)
def test_flash_prefill(width, one_chip, mosaic):
    w = WIDTHS[width]
    q = ((1, w["H"], PROMPT, w["D"]), BF16)
    kv = ((1, w["KV"], PROMPT, w["D"]), BF16)
    pos = ((1, PROMPT), I32)
    assert fa.prefill_kernel_supported(q[0], kv[0])
    _compile(fa.flash_attention_prefill, one_chip, q, kv, kv, pos, pos)


@pytest.mark.parametrize("width", WIDTH_IDS)
def test_flash_decode(width, one_chip, mosaic):
    w = WIDTHS[width]
    q = ((SLOTS, w["H"], 1, w["D"]), BF16)
    kv = ((SLOTS, w["KV"], SEQ, w["D"]), BF16)
    assert fa.decode_kernel_supported(q[0], kv[0])
    _compile(
        fa.flash_attention_decode, one_chip,
        q, kv, kv, ((SLOTS, 1), I32), ((SLOTS, SEQ), I32),
    )


POOL_LAYERS = 4  # the paged kernels take the whole stack and a layer index
LAYER = ((1,), I32)


def _pool(w):
    n_blocks = SLOTS * (SEQ // BLOCK) + SLOTS
    return ((POOL_LAYERS, n_blocks * BLOCK, w["KV"], w["D"]), BF16)


@pytest.mark.parametrize("width", WIDTH_IDS)
def test_paged_prefill(width, one_chip, mosaic):
    w = WIDTHS[width]
    q = ((1, w["H"], PROMPT, w["D"]), BF16)
    pool = _pool(w)
    assert fa.paged_prefill_kernel_supported(q[0], pool[0], BLOCK)
    _compile(
        lambda q, k, v, bt, pos, li: fa.paged_attention_prefill(
            q, k, v, bt, pos, li, block_size=BLOCK
        ),
        one_chip, q, pool, pool, ((1, SEQ // BLOCK), I32), ((1, PROMPT), I32), LAYER,
    )


@pytest.mark.parametrize("width", WIDTH_IDS)
def test_paged_decode(width, one_chip, mosaic):
    w = WIDTHS[width]
    q = ((SLOTS, w["H"], 1, w["D"]), BF16)
    pool = _pool(w)
    assert fa.paged_decode_kernel_supported(q[0], pool[0], BLOCK)
    _compile(
        lambda q, k, v, bt, pos, li: fa.paged_attention_decode(
            q, k, v, bt, pos, li, block_size=BLOCK
        ),
        one_chip, q, pool, pool, ((SLOTS, SEQ // BLOCK), I32), ((SLOTS, 1), I32), LAYER,
    )


@pytest.mark.parametrize("width", WIDTH_IDS)
def test_ragged_paged(width, one_chip, mosaic):
    """Mixed dispatch's packed stream: one 1024-token prompt + 8 decode rows
    padded to the 1040-token rung."""
    w = WIDTHS[width]
    T = PROMPT + 16
    q = ((1, w["H"], T, w["D"]), BF16)
    pool = _pool(w)
    assert ragged_paged_kernel_supported(q[0], pool[0], BLOCK)
    _compile(
        lambda q, k, v, bt, rid, pos, li: ragged_paged_attention(
            q, k, v, bt, rid, pos, li, block_size=BLOCK
        ),
        one_chip, q, pool, pool,
        ((SLOTS + 1, SEQ // BLOCK), I32), ((T,), I32), ((T,), I32), LAYER,
    )


@pytest.mark.parametrize("width", WIDTH_IDS)
def test_kv_commit_rows(width, one_chip, mosaic):
    w = WIDTHS[width]
    L = 16 if width == "1b" else 32
    cache = ((L, SLOTS, w["KV"], SEQ, w["D"]), BF16)
    rows = ((L, SLOTS, w["KV"], 1, w["D"]), BF16)
    assert kv_commit.commit_rows_supported(cache[0], cache[0], rows[0], rows[0])
    _compile(
        kv_commit.kv_commit_rows, one_chip,
        cache, cache, rows, rows, ((SLOTS, 1), I32),
    )


@pytest.mark.parametrize("m", [SLOTS, PROMPT], ids=["decode", "prefill"])
@pytest.mark.parametrize("width", WIDTH_IDS)
def test_fused_mlp(width, m, one_chip, mosaic):
    w = WIDTHS[width]
    hid, inter = w["hidden"], w["inter"]
    assert fp.fused_mlp_supported(m, hid, inter, "silu")
    x = ((m, hid), BF16)
    up = ((hid, inter), BF16)
    down = ((inter, hid), BF16)
    _compile(fp.fused_mlp, one_chip, x, up, up, down)
    L = 2  # the stacked variant indexes a layer of the (L, ...) scan stack
    _compile(
        fp.fused_mlp_stacked, one_chip,
        x, ((L,) + up[0], BF16), ((L,) + up[0], BF16), ((L,) + down[0], BF16),
        ((1,), I32),
    )


@pytest.mark.parametrize("m", [SLOTS, PROMPT], ids=["decode", "prefill"])
@pytest.mark.parametrize("width", WIDTH_IDS)
def test_fused_qkv(width, m, one_chip, mosaic):
    w = WIDTHS[width]
    hid = w["hidden"]
    t = (w["H"] + 2 * w["KV"]) * w["D"]
    assert fp.qkv_matmul_supported(m, hid, t)
    x = ((m, hid), BF16)
    _compile(fp.qkv_matmul, one_chip, x, ((hid, t), BF16))
    _compile(
        fp.qkv_matmul_stacked, one_chip, x, ((2, hid, t), BF16), ((1,), I32)
    )


def _kernel_payloads(lowered_text: str):
    return [ln for ln in lowered_text.splitlines() if "tpu_custom_call" in ln]


def test_kernel_bytes_do_not_depend_on_the_call_path(one_chip, mosaic):
    """A Mosaic kernel rides in its program as serialized bytes that keep
    their MLIR locations, and JAX's default puts the Python traceback of the
    ``pallas_call`` there: the same program lowered from ``compile()`` and
    from ``load()`` then hashes to two persistent-cache keys (seen on the
    v5e for the TKG program, whose commit kernel sits few frames deep).
    Under the setting ``enable_persistent_cache`` makes, the bytes are the
    same from every caller."""
    w = WIDTHS["1b"]
    cache = jax.ShapeDtypeStruct((16, SLOTS, w["KV"], SEQ, w["D"]), BF16, sharding=one_chip)
    rows = jax.ShapeDtypeStruct((16, SLOTS, w["KV"], 1, w["D"]), BF16, sharding=one_chip)
    slots = jax.ShapeDtypeStruct((SLOTS, 1), I32, sharding=one_chip)

    def lower():
        # a fresh jit each time: nothing is served from a lowering cache
        fn = jax.jit(lambda *a: kv_commit.kv_commit_rows(*a))
        return _kernel_payloads(fn.lower(cache, cache, rows, rows, slots).as_text())

    def from_another_caller():
        return lower()

    was = jax.config.jax_include_full_tracebacks_in_locations
    try:
        jax.config.update("jax_include_full_tracebacks_in_locations", True)
        assert lower() != from_another_caller()  # the default: path-dependent
        jax.config.update("jax_include_full_tracebacks_in_locations", False)
        assert lower() == from_another_caller()
    finally:
        jax.config.update("jax_include_full_tracebacks_in_locations", was)


# ---------------------------------------------------------------------------
# Whole step programs over the paged pool: the pool stays in ONE place
# ---------------------------------------------------------------------------

# Qwen2.5-3B-Instruct's widths (benchmark/configs/qwen25-3b.json), its 36
# layers (the layer scan compiles ONE layer body), and the serving stack of the
# `qwen25-3b` cells: 64 decode rows, window 4096, 448 blocks of 128 = a
# (36, 57344, 2, 128) bf16 pool per side, 1.97 GiB for K and V together
QWEN25_3B = dict(
    model_type="qwen2", hidden_size=2048, intermediate_size=11008,
    num_hidden_layers=36, num_attention_heads=16, num_key_value_heads=2,
    vocab_size=151936, rms_norm_eps=1e-6, rope_theta=1000000.0,
    max_position_embeddings=32768, tie_word_embeddings=True, hidden_act="silu",
)
ROWS, WINDOW, POOL_BLOCKS, CTE_BUCKET = 64, 4096, 448, 256

_HLO_LINE = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = ([a-z0-9]+\[[0-9,]*\])\S* ([a-z][a-z\-]*)\(")
_MOVERS = ("copy", "dynamic-slice", "dynamic-update-slice")


def _paged_app(config, devices, **tpu_kwargs):
    """An un-loaded paged serving app of ``config`` on ``devices`` (described
    or real): wrappers built, nothing placed, so its programs lower from
    shapes alone."""
    from nxdi_tpu.config import OnDeviceSamplingConfig, TpuConfig
    from nxdi_tpu.models.registry import get_family
    from nxdi_tpu.parallel.mesh import mesh_from_config
    from nxdi_tpu.runtime.application import TpuModelForCausalLM

    family, cfg_cls = get_family(config["model_type"])
    tc = TpuConfig(
        tp_degree=1, dtype="bfloat16",
        on_device_sampling_config=OnDeviceSamplingConfig(),
        is_block_kv_layout=True, telemetry="off", **tpu_kwargs,
    )
    app = TpuModelForCausalLM(
        "<shapes>", cfg_cls(tc, load_config=lambda: dict(config)), model_family=family
    )
    app.mesh = mesh_from_config(tc, devices=devices)
    app._build_wrappers()
    return app


def _pool_movers(hlo_text, pool_shape):
    """Instructions of the optimised HLO that copy, slice or stack something
    of the pool's shape or of one layer's slice of it."""
    L, S, KV, D = pool_shape
    shapes = {f"bf16[{L},{S},{KV},{D}]", f"bf16[1,{S},{KV},{D}]", f"bf16[{S},{KV},{D}]"}
    found = []
    for line in hlo_text.splitlines():
        m = _HLO_LINE.match(line)
        if not m or m.group(2) not in shapes:
            continue
        name, _, opcode = m.groups()
        if opcode in _MOVERS or (opcode == "fusion" and any(w in name for w in _MOVERS)):
            found.append(f"{opcode} {name} {m.group(2)}")
    return found


@pytest.mark.parametrize(
    "tag", ["token_generation_model", "context_encoding_model"], ids=["tkg64", "cte256"]
)
def test_paged_step_program_keeps_the_pool_in_place(tag, topo, mosaic):
    """The real token-generation program (64 rows) and CTE[256] of a paged
    app at `qwen25-3b` widths: no pool-sized ``temp``, no copy, slice or
    stacking of the pool or of a layer's slice in the optimised HLO, and the
    output pool aliased to the donated input. With the pool as the layer
    scan's xs/ys this read: temp 1.97 GiB, two ``copy`` of the pool, two
    ``dynamic-slice`` and two ``dynamic-update-slice`` fusions a layer."""
    app = _paged_app(
        QWEN25_3B, topo.devices[:1],
        batch_size=ROWS, ctx_batch_size=1, tkg_batch_size=ROWS, seq_len=WINDOW,
        max_context_length=CTE_BUCKET, context_encoding_buckets=[CTE_BUCKET],
        pa_block_size=BLOCK, pa_num_blocks=POOL_BLOCKS,
        attn_kernel_enabled=True, attn_block_tkg_kernel_enabled=True,
    )
    cache = app._cache_struct()
    pool_shape = cache["k"].shape
    assert pool_shape == (36, POOL_BLOCKS * BLOCK, 2, 128)
    pool_bytes = 2 * 2 * 36 * POOL_BLOCKS * BLOCK * 2 * 128  # K and V, bf16

    (compiled,) = app.models[tag].aot_compile(app.build_params_struct(), cache).values()
    memory = compiled.memory_analysis()
    assert memory.temp_size_in_bytes < pool_bytes // 8, memory
    assert memory.alias_size_in_bytes >= pool_bytes, memory
    text = compiled.as_text()
    assert "tpu_custom_call" in text, "no Mosaic kernel in the compiled program"
    assert _pool_movers(text, pool_shape) == []


def _laid_out(app):
    """The app's programs compiled by the load-time layout pass
    (runtime/param_layout.py ``decide``) for abstract params held in the
    default layout: ``(result, param dims, {label: compiled})``."""
    from nxdi_tpu.runtime import param_layout

    tkg = app.models["token_generation_model"]

    def attach(struct, shardings):
        return jax.tree_util.tree_map(
            lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh), struct, shardings)

    params = attach(app.build_params_struct(), tkg._param_shardings)
    cache = attach(app._cache_struct(), tkg._cache_shardings)
    result = param_layout.decide(app, params, cache)
    compiled = {p.label: p._compiled for w in app.models.values() for p in w._programs.values()}
    return result, [tuple(a.shape) for a in jax.tree_util.tree_leaves(params)], compiled


def test_weights_laid_out_for_the_decode_program_are_never_copied_in_it(topo, mosaic):
    """`qwen25-3b`'s step programs after the load-time layout pass: held
    row-major, the q, k and v stacks were sliced a layer at a time inside the
    decode program's layer loop and each slice copied into the layout the dot
    reads (`copy.47`, 0.35 ms of a 12.7 ms step on the v5e); laid out for that
    dot once at load, no program copies a weight, the prefill included."""
    from nxdi_tpu.analysis.hlo import param_moves

    app = _paged_app(
        QWEN25_3B, topo.devices[:1],
        batch_size=ROWS, ctx_batch_size=1, tkg_batch_size=ROWS, seq_len=WINDOW,
        max_context_length=CTE_BUCKET, context_encoding_buckets=[CTE_BUCKET],
        pa_block_size=BLOCK, pa_num_blocks=POOL_BLOCKS,
        attn_kernel_enabled=True, attn_block_tkg_kernel_enabled=True,
    )
    (compiled,) = app.models["token_generation_model"].aot_compile(
        app.build_params_struct(), app._cache_struct()).values()
    dims = [tuple(a.shape) for a in jax.tree_util.tree_leaves(app.build_params_struct())]
    as_held = param_moves(compiled.as_text(), dims)
    assert {m.what for m in as_held} == {"copy of a layer's slice"} and len(as_held) == 3, as_held
    result, dims, programs = _laid_out(app)
    assert result.moved == [f"['layers']['attn']['{w}']['w']" for w in ("k_proj", "q_proj", "v_proj")]
    assert result.kept == [] and result.moved_bytes == 36 * 2048 * (2048 + 2 * 256) * 2
    for label, c in programs.items():
        assert param_moves(c.as_text(), dims) == [], label
        assert c.memory_analysis().temp_size_in_bytes < 2 ** 20, label


def _scans(jaxpr):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan":
            yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _scans(sub)


def test_layer_scan_carries_the_pool_in_every_paged_program():
    """CPU backend, no topology: in the jaxpr of every program of a paged app
    (context encoding, prefix/chunked prefill, token generation, mixed) the
    layer scan has the K and V pools among its CARRY and no xs or ys operand
    of the pool's shape (nor of a per-segment stack of its layers)."""
    tiny = dict(
        model_type="llama", hidden_size=64, intermediate_size=128,
        num_hidden_layers=3, num_attention_heads=4, num_key_value_heads=2,
        vocab_size=256, rms_norm_eps=1e-5, rope_theta=10000.0,
        max_position_embeddings=128, tie_word_embeddings=False, hidden_act="silu",
    )
    app = _paged_app(
        tiny, jax.devices()[:1],
        batch_size=3, ctx_batch_size=1, tkg_batch_size=3, seq_len=64,
        max_context_length=32, pa_block_size=8, pa_num_blocks=32,
        is_prefix_caching=True, mixed_dispatch=True,
    )
    cache = app._cache_struct()
    pool = cache["k"].shape
    params = app.build_params_struct()
    seen = set()
    for tag, wrapper in app.models.items():
        for key, prog in wrapper._programs.items():
            with jax.set_mesh(app.mesh):
                jaxpr = jax.make_jaxpr(prog._fn)(params, cache, wrapper._example_for_key(key))
            layer_scans = [
                e for e in _scans(jaxpr.jaxpr)
                if any(getattr(v.aval, "shape", None) == pool for v in e.invars)
            ]
            assert len(layer_scans) == 1, (tag, key, len(layer_scans))
            (scan,) = layer_scans
            n_consts, n_carry = scan.params["num_consts"], scan.params["num_carry"]
            carry = scan.invars[n_consts:n_consts + n_carry]
            xs, ys = scan.invars[n_consts + n_carry:], scan.outvars[n_carry:]
            assert [v.aval.shape for v in carry].count(pool) == 2, (tag, key)
            assert [v.aval.shape for v in scan.outvars[:n_carry]].count(pool) == 2
            for v in list(xs) + list(ys):
                shape = getattr(v.aval, "shape", ())
                assert shape[1:] != pool[1:], (tag, key, shape)
            seen.add(tag)
    assert {"context_encoding_model", "token_generation_model", "mixed_model"} <= seen, seen
    assert len(seen) >= 4, seen  # + the prefix/chunked prefill program


# -- the paged LATENT pool (PR 30): openPangu-Ultra-MoE's widths as the cell
# `pangu-ultra-moe-ep16.reason-saturated` serves them: one dense + four expert
# layers, 16 of 256 experts held, 128 rows, 2560 blocks of 128 = a
# (5, 327680, 1, 128) rope pool and a (5, 327680, 1, 512) latent pool, 1.95 GiB
def _pangu_share():
    import json

    path = os.path.join(os.path.dirname(__file__), "..", "..", "benchmark", "configs",
                        "pangu-ultra-moe-ep16.json")
    with open(path) as f:
        body = json.load(f)
    own = ("name", "source", "deployment", "reduced", "published", "published_why", "assumed", "benchmark")
    return {k: v for k, v in body.items() if k not in own}, body["benchmark"]


def _a_layers_experts_as_buffers(hlo_text, config):
    """Instructions of the optimised HLO whose result is ONE layer's held
    expert matrix (``(held, hidden, intermediate)`` or its transpose, with or
    without a leading 1) as a buffer of its own: 0.5 GiB a matrix at 16
    experts of 7680 x 2048. Inside a fused computation an instruction is a
    value, not a buffer: an einsum has its layer's slice of the stack there,
    read in place as it computes."""
    e, h, i = config["n_routed_experts"], config["hidden_size"], config["moe_intermediate_size"]
    found, fused = [], False
    for ln in hlo_text.splitlines():
        if ln.endswith("{") and not ln.startswith(" "):
            fused = ln.startswith("%fused_computation")
        elif not fused and (m := re.match(
                rf"\s*(?:ROOT )?%?[\w.\-]+ = bf16\[(1,)?{e},({h},{i}|{i},{h})\]\S* ([a-z\-]+)\(", ln)):
            # a one-layer segment's whole stack is (1, held, ..): handed on, it is no copy
            if not (m.group(1) and m.group(3) in ("parameter", "get-tuple-element", "bitcast")):
                found.append(ln.strip()[:160])
    return found


def test_mla_paged_decode(one_chip, mosaic):
    """The absorbed latent decode kernel at the published widths: 128 heads
    against a 512-wide latent block and a rope block of one lane tile."""
    from nxdi_tpu.ops.kernels import mla_decode

    rows, table = 16, 32

    def fn(q_lat, q_rot, k_pool, c_pool, bt, pos, layer):
        return mla_decode.mla_paged_decode(
            q_lat, q_rot, k_pool, c_pool, bt, pos, layer, block_size=BLOCK, scale=192 ** -0.5)

    bf16, i32 = jnp.bfloat16, jnp.int32
    assert mla_decode.mla_paged_decode_supported((rows, 128, 512), (5, 64 * BLOCK, 1, 128),
                                                 (5, 64 * BLOCK, 1, 512), BLOCK)
    _compile(
        fn, one_chip, ((rows, 128, 512), bf16), ((rows, 128, 64), bf16),
        ((5, 64 * BLOCK, 1, 128), bf16), ((5, 64 * BLOCK, 1, 512), bf16),
        ((rows, table), i32), ((rows,), i32), ((1,), i32),
    )


@pytest.mark.parametrize("rows, form", [(None, "dense"), (1, "sorted")], ids=["the-cells-128-rows", "one-row"])
def test_latent_step_program_keeps_the_pool_in_place(topo, mosaic, rows, form):
    """The real token-generation program (the cell's 128 rows) at the
    configuration's widths: the latent pool aliased from the donated input to
    the output, no pool-sized ``temp`` (the gathered-block XLA decode reads
    2.6 GiB), no copy or slice of either pool, the absorbed kernel in the
    program, and the 16 held experts in the form the layer chose from its
    shapes: DENSE (128 rows x top 8 >= 256 and 16 <= 2 x 8), einsums that read
    a layer's slice of the scan's xs in place, no grouped matmul. The same
    share at ONE row stays sorted, with the layer-stacked weights handed to
    the grouped matmul whole: a scan that sliced a layer's experts out for it
    materialised 0.5 GiB a matrix (``temp`` 506 MiB)."""
    config, b = _pangu_share()
    rows = rows or b["slots"]
    app = _paged_app(
        config, topo.devices[:1],
        batch_size=rows, ctx_batch_size=1, tkg_batch_size=rows, seq_len=b["seq_len"],
        max_context_length=256, context_encoding_buckets=[256],
        pa_block_size=BLOCK, pa_num_blocks=b["pa_num_blocks"],
        attn_kernel_enabled=True, attn_block_tkg_kernel_enabled=True, **b.get("tpu_config", {}),
    )
    cache = app._cache_struct()
    slots = b["pa_num_blocks"] * BLOCK
    assert cache["k"].shape == (5, slots, 1, 128) and cache["v"].shape == (5, slots, 1, 512)
    pool_bytes = 5 * slots * (128 + 512) * 2

    (compiled,) = app.models["token_generation_model"].aot_compile(
        app.build_params_struct(), cache).values()
    memory = compiled.memory_analysis()
    assert memory.temp_size_in_bytes < pool_bytes // 8, memory
    assert memory.alias_size_in_bytes >= pool_bytes, memory
    assert memory.argument_size_in_bytes < 11.5 * 2 ** 30, memory
    text = compiled.as_text()
    assert "mla_paged_decode" in text and "tpu_custom_call" in text
    movers = _pool_movers(text, cache["k"].shape) + _pool_movers(text, cache["v"].shape)
    if rows == 1:  # ONE row's write is a dynamic-update-slice of the carried pool, in place
        movers = [m for m in movers if not m.startswith("dynamic-update-slice ")]
    assert movers == []
    (prog,) = app.models["token_generation_model"]._programs.values()
    assert set(prog.attention_strategies) == {"tkg_mla_paged_kernel"}
    assert app.models["token_generation_model"].arch.moe.dispatch is None, "nothing pinned"
    assert prog.expert_forms == (form,)
    assert f"moe.experts.{form}" in text and ("ragged-dot" in text) == (form == "sorted")
    assert _a_layers_experts_as_buffers(text, config) == []


def test_latent_share_prefills_its_largest_bucket_compact(topo, mosaic):
    """CTE[1024] of the cell, the bucket that sets its tail (ROADMAP D13: the
    bucket with the largest ``temp`` had no bound): the 16 held experts in the
    form the layer chose from its shapes, COMPACT (1024 rows >= 512, 256 slots
    an expert), einsums over the slots, no grouped matmul; the trips are a
    loop inside the layer scan, so the expert weights come layer-stacked and
    each einsum slices its layer inside the loop: no layer's experts as a
    buffer of their own (sliced out of the scan's xs for the loop, or hoisted
    out of it: ``temp`` 1506 MiB). ``temp`` reads 213 MiB where the dense
    form's program read 166: the float32 rows the slots' outputs are added
    into across trips, 30 MiB at 1024 x 7680, and the loop's copies of them."""
    config, b = _pangu_share()
    app = _paged_app(
        config, topo.devices[:1],
        batch_size=b["slots"], ctx_batch_size=1, tkg_batch_size=b["slots"], seq_len=b["seq_len"],
        max_context_length=1024, context_encoding_buckets=[1024],
        pa_block_size=BLOCK, pa_num_blocks=b["pa_num_blocks"],
        attn_kernel_enabled=True, attn_block_tkg_kernel_enabled=True, **b.get("tpu_config", {}),
    )
    cache = app._cache_struct()
    pool_bytes = 5 * b["pa_num_blocks"] * BLOCK * (128 + 512) * 2
    (compiled,) = app.models["context_encoding_model"].aot_compile(
        app.build_params_struct(), cache).values()
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes >= pool_bytes, memory
    assert memory.temp_size_in_bytes < 224 * 2 ** 20, memory
    text = compiled.as_text()
    (prog,) = app.models["context_encoding_model"]._programs.values()
    assert app.models["context_encoding_model"].arch.moe.dispatch is None, "nothing pinned"
    assert prog.expert_forms == ("compact",)
    assert "moe.experts.compact" in text and "moe.experts.dense" not in text and "ragged-dot" not in text
    assert _a_layers_experts_as_buffers(text, config) == []
    assert _pool_movers(text, cache["k"].shape) + _pool_movers(text, cache["v"].shape) == []


def test_layer_scan_carries_the_latent_pool():
    """CPU backend: in both programs of a paged ``pangu_ultra_moe`` app each
    segment's layer scan (dense head, expert tail) has the rope pool and the
    latent pool among its CARRY, never as xs or ys; token generation carries
    the held-pair count beside them."""
    toy = dict(
        model_type="pangu_ultra_moe", hidden_size=64, intermediate_size=128, num_hidden_layers=3,
        first_k_dense_replace=1, num_attention_heads=4, num_key_value_heads=4, vocab_size=256,
        q_lora_rank=32, kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
        moe_intermediate_size=32, n_routed_experts=4, n_routed_experts_total=16,
        n_shared_experts=1, num_experts_per_tok=2, routed_scaling_factor=2.5, rms_norm_eps=1e-5,
        rope_theta=10000.0, max_position_embeddings=128, tie_word_embeddings=False,
    )
    app = _paged_app(
        toy, jax.devices()[:1],
        batch_size=3, ctx_batch_size=1, tkg_batch_size=3, seq_len=64,
        max_context_length=32, pa_block_size=8, pa_num_blocks=32,
    )
    cache = app._cache_struct()
    k_pool, c_pool = cache["k"].shape, cache["v"].shape
    assert k_pool == (3, 256, 1, 128) and c_pool == (3, 256, 1, 32)  # the rope key in a lane tile
    params = app.build_params_struct()
    for tag in ("context_encoding_model", "token_generation_model"):
        wrapper = app.models[tag]
        for key, prog in wrapper._programs.items():
            with jax.set_mesh(app.mesh):
                jaxpr = jax.make_jaxpr(prog._fn)(params, cache, wrapper._example_for_key(key))
            layer_scans = [
                e for e in _scans(jaxpr.jaxpr)
                if any(getattr(v.aval, "shape", None) == c_pool for v in e.invars)
            ]
            assert len(layer_scans) == 2, (tag, key, len(layer_scans))  # one a segment
            for scan in layer_scans:
                n_consts, n_carry = scan.params["num_consts"], scan.params["num_carry"]
                carry = [v.aval.shape for v in scan.invars[n_consts:n_consts + n_carry]]
                assert carry.count(k_pool) == 1 and carry.count(c_pool) == 1, (tag, carry)
                assert ((2,) in carry) == (tag == "token_generation_model"), (tag, carry)
                for v in list(scan.invars[n_consts + n_carry:]) + list(scan.outvars[n_carry:]):
                    shape = getattr(v.aval, "shape", ())
                    assert shape[1:] not in (k_pool[1:], c_pool[1:]), (tag, key, shape)


# -- a pool and a store per slot (PR 35): MiMo-V2-Flash's widths as the cell
# `mimo-v2-flash-ep16.longctx-saturated` serves them: layers 0-6 (2 full, 5 window), 16 of 256
# experts held, 128 rows, 6912 blocks of 128: the full layers' pool, its 192-wide keys as two
# lane tiles (4, 884736, 4, 128) beside the values (2, 884736, 4, 128), and the window layers'
# 128 ring rows a slot (5, 128, 8, 128, 192 | 128)
def _mimo_share():
    import json

    path = os.path.join(os.path.dirname(__file__), "..", "..", "benchmark", "configs",
                        "mimo-v2-flash-ep16.json")
    with open(path) as f:
        body = json.load(f)
    own = ("name", "source", "deployment", "reduced", "published", "published_why", "assumed", "benchmark")
    return {k: v for k, v in body.items() if k not in own}, body["benchmark"]


def test_paged_decode_with_key_tiles_and_a_value_width(one_chip, mosaic):
    """The paged decode kernel at the published widths: 64 query heads against 4
    kv heads, a 192-wide key kept as two lane tiles (tile j of layer l at pool
    layer j * 2 + l), values 128 wide; no temporary beside the pools."""
    rows, table, slots = 128, 65, 1024 * BLOCK

    def fn(q, k, v, bt, pos, layer):
        return fa.paged_attention_decode(q, k, v, bt, pos, layer, block_size=BLOCK, scale=192 ** -0.5)

    q, k, v = (rows, 64, 1, 256), (4, slots, 4, 128), (2, slots, 4, 128)
    assert fa.paged_decode_kernel_supported(q, k, BLOCK, v)
    assert not fa.paged_decode_kernel_supported((rows, 64, 1, 256), (2, slots, 4, 128), BLOCK, v)  # one tile
    text = _compile(fn, one_chip, (q, BF16), (k, BF16), (v, BF16), ((rows, table), I32),
                    ((rows, 1), I32), LAYER)
    assert "paged_attention_decode" in text


@pytest.mark.parametrize("kv, window, sink", [(4, None, False), (8, 128, True)], ids=["full", "window-sink"])
def test_flash_prefill_with_a_value_width_and_a_sink(kv, window, sink, one_chip, mosaic):
    """The prefill kernel over a 4096-token prompt: keys 192, values 128 wide;
    the window layers' 128-token window and one learned sink a head."""
    S = 4096
    q, k, v = (1, 64, S, 192), (1, kv, S, 192), (1, kv, S, 128)
    assert fa.prefill_kernel_supported(q, k)

    def fn(q, k, v, pos, sinks):
        return fa.flash_attention_prefill(q, k, v, pos, pos, sliding_window=window,
                                          sink=sinks if sink else None)

    _compile(fn, one_chip, (q, BF16), (k, BF16), (v, BF16), ((1, S), I32), ((64,), jnp.float32))


def test_two_store_step_program_keeps_the_pool_in_place(topo, mosaic):
    """The real token-generation program (the cell's 128 rows) at the
    configuration's widths: both pools aliased from the donated input to the
    output, no copy or slice of a pool (with the key rows as (.., 4, 256) the
    kernel's (slots * KV, width) view was a 3.4 GiB copy a step; with a
    one-layer segment left to the compiler's rematerialisation, a second key
    pool), ``temp`` far under a pool's size, the paged kernel for the full
    layers and the two-part XLA form over the ring rows for the window layers,
    the held experts dense."""
    from nxdi_tpu.config import OnDeviceSamplingConfig, TpuConfig
    from nxdi_tpu.models.registry import get_family
    from nxdi_tpu.parallel.mesh import mesh_from_config

    config, b = _mimo_share()
    family, cfg_cls = get_family(config["model_type"])
    tc = TpuConfig(
        tp_degree=1, dtype="bfloat16", on_device_sampling_config=OnDeviceSamplingConfig(),
        is_block_kv_layout=True, telemetry="off",
        batch_size=b["slots"], ctx_batch_size=1, tkg_batch_size=b["slots"], seq_len=b["seq_len"],
        max_context_length=256, context_encoding_buckets=[256],
        pa_block_size=BLOCK, pa_num_blocks=b["pa_num_blocks"],
        attn_kernel_enabled=True, attn_block_tkg_kernel_enabled=True,
    )
    app = family.APPLICATION_CLS(
        "<shapes>", cfg_cls(tc, load_config=lambda: dict(config)), model_family=family)
    app.mesh = mesh_from_config(tc, devices=topo.devices[:1])
    app._build_wrappers()
    cache = app._cache_struct()
    slots = b["pa_num_blocks"] * BLOCK
    assert cache["k"].shape == (4, slots, 4, 128) and cache["v"].shape == (2, slots, 4, 128)
    assert cache["k_swa"].shape == (5, 128, 8, 128, 192) and cache["v_swa"].shape == (5, 128, 8, 128, 128)
    pool_bytes = (4 + 2) * slots * 4 * 128 * 2
    store_bytes = 5 * 128 * 8 * 128 * (192 + 128) * 2

    # the programs as load() compiles them: the weights in the layout the decode program reads
    from nxdi_tpu.analysis.hlo import param_moves

    result, dims, programs = _laid_out(app)
    compiled = programs["token_generation_model[8320]"]
    assert result.moved == [f"['segments'][{s}]['attn']['{w}_proj']['w']"
                            for s in range(4) for w in "kqv"]  # q, k, v of each segment
    assert result.kept == []
    assert [param_moves(c.as_text(), dims) for c in programs.values()] == [[], []]
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes >= pool_bytes + store_bytes, memory
    # 484 MiB with the weights held row-major: a window layer's q, k and v slices relaid inside the
    # layer loop and the single-layer segments' projections relaid at the entry. 436 MiB laid out
    # for the decode program; what is left is no weight's (param_moves finds none): a window layer's
    # gathered ring rows and its float32 scores (PERF.md section 7)
    assert memory.temp_size_in_bytes < 448 * 2 ** 20, memory
    assert memory.argument_size_in_bytes < 12.0 * 2 ** 30, memory
    text = compiled.as_text()
    assert "paged_attention_decode" in text and "kv_commit_rows" in text
    assert _pool_movers(text, cache["k"].shape) + _pool_movers(text, cache["v"].shape) == []
    views = rf"bf16\[\d+,{slots * 4},(128|256)\]\S* (copy|reshape)\("  # the kernel's (L, slots * KV, width)
    assert not re.search(views, text), "a pool's view copied"
    (prog,) = app.models["token_generation_model"]._programs.values()
    assert set(prog.attention_strategies) == {"tkg_paged_kernel", "tkg_two_part_xla"}
    assert prog.expert_forms == ("dense",)
    assert "layers.full" in text and "layers.window" in text and "attn.sink" in text
    # the ring store in ONE memory layout in every program (rows minor): left to each program's
    # choice a prefill relaid it on the way in and the next decode step on the way back
    prefill = programs["context_encoding_model[256]"]
    # laid out, a prefill kept a late segment's q_proj in fast memory between executions (XLA's
    # cross-program prefetch) and lost the placements of its own buffers: it takes none now
    assert "cross_program_prefetch_index" not in prefill.as_text()
    for name in ("k_swa", "v_swa", "k", "v"):
        decode_layout = compiled.input_formats[0][1][name].layout
        assert prefill.input_formats[0][1][name].layout == decode_layout, name
        if name.endswith("swa"):
            assert decode_layout.major_to_minor == (0, 1, 2, 4, 3), decode_layout


def test_two_store_share_prefills_its_largest_bucket_compact(topo, mosaic):
    """CTE[4096] of the cell, the one bucket its prompts of 2048-4096 take and
    the largest ``temp`` of any program the benchmark compiles: the held
    experts COMPACT in both kinds of segment (4096 rows, 256 slots an expert),
    no grouped matmul, no layer's experts as a buffer of their own, and
    ``temp`` what the dense form's program read (869 MiB: the attention's)."""
    from nxdi_tpu.config import OnDeviceSamplingConfig, TpuConfig
    from nxdi_tpu.models.registry import get_family
    from nxdi_tpu.parallel.mesh import mesh_from_config

    config, b = _mimo_share()
    family, cfg_cls = get_family(config["model_type"])
    tc = TpuConfig(
        tp_degree=1, dtype="bfloat16", on_device_sampling_config=OnDeviceSamplingConfig(),
        is_block_kv_layout=True, telemetry="off",
        batch_size=b["slots"], ctx_batch_size=1, tkg_batch_size=b["slots"], seq_len=b["seq_len"],
        max_context_length=4096, context_encoding_buckets=[4096],
        pa_block_size=BLOCK, pa_num_blocks=b["pa_num_blocks"],
        attn_kernel_enabled=True, attn_block_tkg_kernel_enabled=True,
    )
    app = family.APPLICATION_CLS(
        "<shapes>", cfg_cls(tc, load_config=lambda: dict(config)), model_family=family)
    app.mesh = mesh_from_config(tc, devices=topo.devices[:1])
    app._build_wrappers()
    cache = app._cache_struct()
    (compiled,) = app.models["context_encoding_model"].aot_compile(
        app.build_params_struct(), cache).values()
    memory = compiled.memory_analysis()
    assert memory.temp_size_in_bytes < 880 * 2 ** 20, memory
    text = compiled.as_text()
    (prog,) = app.models["context_encoding_model"]._programs.values()
    assert prog.expert_forms == ("compact",)
    assert "moe.experts.compact" in text and "moe.experts.dense" not in text and "ragged-dot" not in text
    assert _a_layers_experts_as_buffers(text, config) == []
    assert _pool_movers(text, cache["k"].shape) + _pool_movers(text, cache["v"].shape) == []


# -- PR 37: a stage of block-sparse and lightning layers: pool, index and state in place --


def _sala_stage():
    import json

    path = os.path.join(os.path.dirname(__file__), "..", "..", "benchmark", "configs",
                        "minicpm-sala-l12.json")
    with open(path) as f:
        body = json.load(f)
    own = ("name", "source", "deployment", "reduced", "published", "published_why", "assumed", "benchmark")
    return {k: v for k, v in body.items() if k not in own}, body["benchmark"]


def _sala_app(topo, bucket):
    from nxdi_tpu.config import OnDeviceSamplingConfig, TpuConfig
    from nxdi_tpu.models.registry import get_family
    from nxdi_tpu.parallel.mesh import mesh_from_config

    config, b = _sala_stage()
    family, cfg_cls = get_family(config["model_type"])
    tc = TpuConfig(
        tp_degree=1, dtype="bfloat16", on_device_sampling_config=OnDeviceSamplingConfig(),
        is_block_kv_layout=True, telemetry="off",
        batch_size=b["slots"], ctx_batch_size=1, tkg_batch_size=b["slots"], seq_len=b["seq_len"],
        max_context_length=bucket, context_encoding_buckets=[bucket],
        pa_block_size=b["pa_block_size"], pa_num_blocks=b["pa_num_blocks"],
        attn_kernel_enabled=True, attn_block_tkg_kernel_enabled=True,
    )
    app = family.APPLICATION_CLS(
        "<shapes>", cfg_cls(tc, load_config=lambda: dict(config)), model_family=family)
    app.mesh = mesh_from_config(tc, devices=topo.devices[:1])
    app._build_wrappers()
    return app, b


def _store_movers(hlo_text, cache, movers=_MOVERS):
    """Copies, slices or stackings of something the size of a whole cache leaf
    (in any of its views), in the optimised HLO."""
    found = []
    for name, s in cache.items():
        n = int(np.prod(s.shape))
        for line in hlo_text.splitlines():
            m = _HLO_LINE.match(line)
            if not m:
                continue
            dims = re.match(r"\w+\[([\d,]+)\]", m.group(2))
            if not dims or int(np.prod([int(d) for d in dims.group(1).split(",")])) != n:
                continue
            inst, _, opcode = m.groups()
            if opcode in movers or (opcode == "fusion" and any(w in inst for w in movers)):
                found.append(f"{name}: {opcode} {inst} {m.group(2)}")
    return found


def test_sparse_linear_stage_decodes_with_pool_index_and_state_in_place(topo, mosaic):
    """The cell's real token-generation program (32 rows, 24 640 positions) at the
    published widths: pool, index and state aliased from the donated input to the
    output, no copy of any of them in any view (the index as (L, slots, KV, rows,
    D) was relaid whole on the way in: 76 MB a step), ``temp`` tens of MiB, the
    paged decode kernel over the compact tables and the state's in-place kernel."""
    app, b = _sala_app(topo, 256)
    cache = app._cache_struct()
    blocks = b["pa_num_blocks"] * b["pa_block_size"]
    assert cache["k"].shape == cache["v"].shape == (3, blocks, 2, 128)
    assert cache["kc"].shape == (3, 32, 1552, 2, 128) and cache["lin_state"].shape == (9, 33, 32, 128, 128)
    held = sum(int(np.prod(s.shape)) * s.dtype.itemsize for s in cache.values())
    assert held == pytest.approx(3.115e9, rel=0.01)  # pool 2.42 GB, index 0.08, state 0.62

    (compiled,) = app.models["token_generation_model"].aot_compile(
        app.build_params_struct(), cache).values()
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes >= held, memory
    assert memory.temp_size_in_bytes < 64 * 2 ** 20, memory
    assert memory.argument_size_in_bytes < 10.3 * 2 ** 30, memory  # 7.86 GB of weights + the caches
    text = compiled.as_text()
    assert "paged_attention_decode" in text and "lightning_decode_step" in text
    assert _store_movers(text, cache) == []
    (prog,) = app.models["token_generation_model"]._programs.values()
    assert set(prog.attention_strategies) == {"tkg_paged_kernel"}
    for scope in ("layers.sparse", "layers.lightning", "attn.index", "attn.select", "lin.step", "lin.out"):
        assert scope in text, scope
    # every store in ONE memory layout in both kinds of program
    (prefill,) = app.models["context_encoding_model"].aot_compile(
        app.build_params_struct(), cache).values()
    for name in cache:
        assert prefill.input_formats[0][1][name].layout == compiled.input_formats[0][1][name].layout, name


def test_sparse_linear_stage_prefills_16k_beside_what_it_holds(topo, mosaic):
    """CTE[16384], the bucket every prompt of the cell takes: the flash kernel
    with the selection's mask operand, the chunked linear attention in XLA, and
    ``temp`` that leaves the 16 GB chip room beside 10.2 GiB of arguments."""
    app, _ = _sala_app(topo, 16384)
    cache = app._cache_struct()
    (compiled,) = app.models["context_encoding_model"].aot_compile(
        app.build_params_struct(), cache).values()
    memory = compiled.memory_analysis()
    assert memory.temp_size_in_bytes < 2.5 * 2 ** 30, memory  # 2.27 GiB: the MLP's, the scores' and the float32 q, k, v's
    assert memory.temp_size_in_bytes + memory.argument_size_in_bytes < 13.0 * 2 ** 30, memory
    text = compiled.as_text()
    assert "flash_attention_prefill" in text and "lin.chunk" in text and "attn.select" in text
    # a slot's index rows and state land by a dynamic-update-slice of the donated store: no copy of
    # the pool or of the state. The 76 MB index alone is relaid KV-major for the prompt's rows and
    # back, once a prefill of ~1 s (PERF.md section 7)
    big = {name: s for name, s in cache.items() if name != "kc"}
    assert _store_movers(text, big, ("copy",)) == []
    (prog,) = app.models["context_encoding_model"]._programs.values()
    assert set(prog.attention_strategies) == {"cte_flash_kernel"}


# -- a share of KDA and gated GQA layers: pool, state and window in place, weights laid out --


@pytest.fixture(scope="module")
def kda_share(topo):
    """``solar-open2-ep8``'s token-generation program and CTE[1024], the bucket
    its longest prompts take, compiled and installed by the load-time layout
    pass (``_laid_out``) with Mosaic kernels: ``(cache struct, param dims,
    {label: compiled}, app)``."""
    import json

    from jax.experimental.compilation_cache import compilation_cache as cc

    from nxdi_tpu.config import OnDeviceSamplingConfig, TpuConfig
    from nxdi_tpu.models.registry import get_family
    from nxdi_tpu.parallel.mesh import mesh_from_config

    path = os.path.join(os.path.dirname(__file__), "..", "..", "benchmark", "configs", "solar-open2-ep8.json")
    with open(path) as f:
        body = json.load(f)
    own = ("name", "source", "deployment", "reduced", "published", "published_why", "assumed", "benchmark")
    config, b = {k: v for k, v in body.items() if k not in own}, body["benchmark"]
    family, cfg_cls = get_family(config["model_type"])
    tc = TpuConfig(
        tp_degree=1, dtype="bfloat16", on_device_sampling_config=OnDeviceSamplingConfig(),
        is_block_kv_layout=True, telemetry="off",
        batch_size=b["slots"], ctx_batch_size=1, tkg_batch_size=b["slots"], seq_len=b["seq_len"],
        max_context_length=1024, context_encoding_buckets=[1024],
        pa_block_size=b["pa_block_size"], pa_num_blocks=b["pa_num_blocks"],
        attn_kernel_enabled=True, attn_block_tkg_kernel_enabled=True,
    )
    app = family.APPLICATION_CLS("<shapes>", cfg_cls(tc, load_config=lambda: dict(config)), model_family=family)
    app.mesh = mesh_from_config(tc, devices=topo.devices[:1])
    app._build_wrappers()
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    interpret = mode.interpret
    mode.interpret = lambda: False
    try:
        _, dims, programs = _laid_out(app)
    finally:
        mode.interpret = interpret
        jax.config.update("jax_enable_compilation_cache", was)
        cc.reset_cache()
    return app._cache_struct(), dims, programs, app


def test_kda_share_decodes_with_pool_state_and_window_in_place(kda_share):
    """128 rows over 2688 positions at the published widths: the pool, the
    float32 state (1.62 GB) and the convolution windows aliased from the
    donated input to the output with no copy of any of them, ``temp`` of 33
    MiB, the paged decode kernel for the GQA layer and the state's in-place
    kernel for the KDA layers, the experts in the dense form (128 rows x top 8
    >= 320 and under twice the ridge: every held expert streams once, read in
    place from the scan's xs, and no grouped matmul is left), and no weight
    copied after the layout pass."""
    from nxdi_tpu.analysis.hlo import param_moves

    cache, dims, programs, app = kda_share
    assert cache["kda_state"].shape == (3, 129, 64, 128, 128) and cache["kda_conv"].shape == (3, 129, 3, 3, 8192)
    held = sum(int(np.prod(s.shape)) * s.dtype.itemsize for s in cache.values())
    assert held == pytest.approx(3.02e9, rel=0.01)  # pool 1.34 GB, state 1.62, windows 0.06
    (label,) = [k for k in programs if k.startswith("token_generation_model")]
    compiled = programs[label]
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes >= held, memory
    # 33 MiB compiled (37 in the sorted form): the dense form's (40, 128, 1280) activations are ~13 MB
    assert memory.temp_size_in_bytes < 40 * 2 ** 20, memory
    assert memory.argument_size_in_bytes < 9.25 * 2 ** 30, memory  # 6.62 GB of weights + 3.02 GB held
    text = compiled.as_text()
    assert "paged_attention_decode" in text and "kda_decode_step" in text
    assert _store_movers(text, cache) == []
    assert param_moves(text, dims) == []
    (prog,) = app.models["token_generation_model"]._programs.values()
    assert set(prog.attention_strategies) == {"tkg_paged_kernel"} and set(prog.expert_forms) == {"dense"}
    assert "ragged-dot" not in text and "moe.experts.dense" in text
    for scope in ("layers.kda", "kda.conv", "kda.gates", "kda.step", "kda.out"):
        assert scope in text, scope


def test_kda_share_prefills_1024_beside_what_it_holds(kda_share):
    """CTE[1024]: the flash kernel for the GQA layer, the chunked KDA prefill in
    XLA (pairwise decays a chunk of 16), the state and window written into the
    donated stores with no copy, every store in the decode program's layout,
    ``temp`` (424 MiB) that leaves the 16 GB chip room, no weight copied, and
    the experts in the compact form (256 slots a held expert: twice them is no
    more than the 1024 rows)."""
    from nxdi_tpu.analysis.hlo import param_moves

    cache, dims, programs, app = kda_share
    prefill = programs["context_encoding_model[1024]"]
    memory = prefill.memory_analysis()
    assert memory.temp_size_in_bytes < 0.75 * 2 ** 30, memory
    assert memory.temp_size_in_bytes + memory.argument_size_in_bytes < 12.0 * 2 ** 30, memory
    text = prefill.as_text()
    assert "flash_attention_prefill" in text and "kda.chunk" in text and "kda.state" in text
    assert _store_movers(text, cache, ("copy",)) == []
    assert param_moves(text, dims) == []
    (prog,) = app.models["context_encoding_model"]._programs.values()
    assert set(prog.attention_strategies) == {"cte_flash_kernel"} and set(prog.expert_forms) == {"compact"}
    assert "ragged-dot" not in text and "moe.experts.compact" in text
    (decode,) = [c for k, c in programs.items() if k.startswith("token_generation_model")]
    for name in cache:
        assert prefill.input_formats[0][1][name].layout == decode.input_formats[0][1][name].layout, name


# -- PR 38: a grid step of the lane-wide paged decode kernel scores its chunk of table entries in
# ONE dot and ONE softmax update; the three geometries the cells run it at:
# (rows, q heads, key width, key pool layers, value pool layers, kv heads, block, table width, pool blocks)
PAGED_DECODE_GEOMETRIES = {
    # qwen25-3b: 36 layers, GQA 16 / 2 x 128, blocks of 128, a 4096 window
    "two-interleaved-heads": (64, 16, 128, 36, 36, 2, 128, 32, 448),
    # minicpm-sala-l12's sparse layers: a (row, KV head) a kernel row, G 16 over one-head blocks of 64, a
    # compact table of 64 entries in a 128-wide one, the pool 12 288 blocks of two heads
    "one-head-blocks": (64, 16, 128, 3, 3, 1, 64, 128, 24576),
    # mimo-v2-flash-ep16's full layers: 64 / 4 heads, keys 192 kept as two lane tiles, values 128
    "two-key-tiles": (128, 64, 256, 4, 2, 4, 128, 65, 6912),
}


@pytest.mark.parametrize("geometry", list(PAGED_DECODE_GEOMETRIES))
def test_paged_decode_chunk_in_one_dot_lowers(geometry, one_chip, mosaic):
    """Each geometry lowers through Mosaic with the chunk's float32 score tile
    (H, 8 x block x KV) and both buffers of its blocks inside the scoped VMEM
    the kernel asks no more of (at two key tiles: 64 x 4096 beside 6 MiB)."""
    rows, H, D, key_layers, value_layers, KV, bs, table, blocks = PAGED_DECODE_GEOMETRIES[geometry]
    q = (rows, H, 1, D)
    k = (key_layers, blocks * bs, KV, 128)
    v = (value_layers, blocks * bs, KV, 128)
    assert fa.paged_decode_kernel_supported(q, k, bs, v)

    def fn(q, k, v, bt, pos, layer):
        return fa.paged_attention_decode(q, k, v, bt, pos, layer, block_size=bs, scale=0.1)

    text = _compile(fn, one_chip, (q, BF16), (k, BF16), (v, BF16), ((rows, table), I32),
                    ((rows, 1), I32), LAYER)
    assert "paged_attention_decode" in text


def _mosaic_bodies(lowered_text):
    """{kernel name: its Mosaic module as MLIR text WITHOUT locations}, from a
    lowered program's text: the body a ``tpu_custom_call`` carries is MLIR
    bytecode that keeps each op's file and line, which move with every edit of
    the file around it."""
    import base64

    from jax._src.interpreters import mlir as jax_mlir
    from jax._src.lib.mlir import ir

    bodies = {}
    for line in lowered_text.splitlines():
        body = re.search(r"body\\22: \\22([A-Za-z0-9+/=]+)", line)
        if "tpu_custom_call" in line and body:
            ctx = jax_mlir.make_ir_context()
            ctx.allow_unregistered_dialects = True
            module = ir.Module.parse(base64.b64decode(body.group(1)), ctx)
            name = re.search(r'kernel_name = "([\w.]+)"', line).group(1)
            bodies[name] = module.operation.get_asm(enable_debug_info=False)
    return bodies


#: sha256 of ``mla_paged_decode``'s Mosaic body at the shapes below, taken from
#: the parent of PR 38 (0707719): the block chain it shares with the paged
#: decode kernel gained a per-chunk hook there, and the latent kernel, which
#: keeps its per-page ``block``, must lower to the same body (pangu's control)
MLA_BODY_BEFORE_PR38 = "33e4228e126547e777b4a0b42efdd7ea26f50642de8f4ac91974152e764a0e65"


def test_mla_paged_decode_body_is_the_parents(one_chip, mosaic):
    import hashlib

    from nxdi_tpu.ops.kernels import mla_decode

    rows, table = 16, 32

    def fn(q_lat, q_rot, k_pool, c_pool, bt, pos, layer):
        return mla_decode.mla_paged_decode(
            q_lat, q_rot, k_pool, c_pool, bt, pos, layer, block_size=BLOCK, scale=192 ** -0.5)

    shapes = [((rows, 128, 512), BF16), ((rows, 128, 64), BF16), ((5, 64 * BLOCK, 1, 128), BF16),
              ((5, 64 * BLOCK, 1, 512), BF16), ((rows, table), I32), ((rows,), I32), ((1,), I32)]
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip) for shape, dtype in shapes]
    bodies = _mosaic_bodies(jax.jit(fn).lower(*args).as_text())
    assert list(bodies) == ["mla_paged_decode"]
    assert hashlib.sha256(bodies["mla_paged_decode"].encode()).hexdigest() == MLA_BODY_BEFORE_PR38


# -- a post-normed window/full share: K-EXAONE-236B-A23B's widths as the cell
# `k-exaone-ep8.longctx-saturated` serves them: layers 0-4 (4 window with RoPE, 1 full NoPE),
# 16 of 128 experts held + a shared one, 128 rows, 6912 blocks of 128: the full layer's pool
# (1, 884736, 8, 128) twice and the window layers' 128 ring rows a slot (4, 128, 8, 128, 128)
def test_window_full_share_with_post_norms_compiles_its_decode_step_in_place(topo, mosaic):
    """The token-generation program at the configuration's widths through the
    load-time layout pass: the pool and the ring rows aliased from the donated
    input to the output, no copy of either or of a weight, ``temp`` far under a
    pool's size, the paged kernel for the full layer and the two-part XLA form
    over the ring rows for the window layers, the held experts dense, and the
    ring's commit under the window layers' scope."""
    import json

    from benchmark import serving_app
    from nxdi_tpu.analysis.hlo import param_moves
    from nxdi_tpu.parallel.mesh import mesh_from_config

    path = os.path.join(os.path.dirname(__file__), "..", "..", "benchmark", "configs", "k-exaone-ep8.json")
    with open(path) as f:
        body = json.load(f)
    app = serving_app.build_app(body, [256], seed=0)
    app.mesh = mesh_from_config(app.tpu_config, devices=topo.devices[:1])
    app._build_wrappers()
    cache = app._cache_struct()
    assert cache["k"].shape == (1, 884736, 8, 128) and cache["k_swa"].shape == (4, 128, 8, 128, 128)
    result, dims, programs = _laid_out(app)
    assert result.kept == []
    assert [param_moves(c.as_text(), dims) for c in programs.values()] == [[], []]
    compiled = programs["token_generation_model[8320]"]
    memory = compiled.memory_analysis()
    pool_bytes = 2 * 884736 * 8 * 128 * 2
    store_bytes = 2 * 4 * 128 * 8 * 128 * 128 * 2
    assert memory.alias_size_in_bytes >= pool_bytes + store_bytes, memory
    assert memory.temp_size_in_bytes < 400 * 2 ** 20, memory  # 325 MiB compiled
    text = compiled.as_text()
    assert "paged_attention_decode" in text and "kv_commit_rows" in text
    assert _pool_movers(text, cache["k"].shape) + _pool_movers(text, cache["v"].shape) == []
    (prog,) = app.models["token_generation_model"]._programs.values()
    assert set(prog.attention_strategies) == {"tkg_paged_kernel", "tkg_two_part_xla"}
    assert prog.expert_forms == ("dense",)
    ops = set(re.findall(r'op_name="([^"]*)"', text))
    assert any("/layers.window/kv.write/" in op for op in ops)
    assert any("/layers.full/" in op and "/attn.core/" in op for op in ops)
