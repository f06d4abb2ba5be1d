"""The two programs kept out of the persistent compilation cache
(runtime/model_wrapper.py): jax 0.9.0 / libtpu 0.0.34 hands an executable
served from that cache back with its output layouts lost, so a
layout-changing identity, and every step program over the block KV layout
(whose preferred pool layout is not the default one), compile under a name no
cache entry has, in a window in which nothing is written."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from nxdi_tpu.runtime import model_wrapper as mw


def test_outside_the_persistent_cache_window():
    was = jax.config.jax_persistent_cache_min_compile_time_secs
    with mw._outside_the_persistent_cache("toy[8]") as a:
        assert jax.config.jax_persistent_cache_min_compile_time_secs == 1e9
    with pytest.raises(RuntimeError):
        with mw._outside_the_persistent_cache("toy[8]") as b:
            raise RuntimeError("compile failed")
    assert jax.config.jax_persistent_cache_min_compile_time_secs == was
    head = f"toy_8__{mw._PROCESS_TOKEN}_"
    assert a != b and a.startswith(head) and b.startswith(head)


def test_relayout_is_an_identity_of_its_own_name_kept_per_format():
    x = jnp.arange(12.0).reshape(3, 4)
    before = len(mw._RELAYOUTS)
    y = mw._relayout(x, x.format)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(x))
    assert y.format == x.format
    mw._relayout(x, x.format)
    assert len(mw._RELAYOUTS) == before + 1  # the second call reused the first
    move = mw._RELAYOUTS[(x.format, x.format, x.shape, x.dtype)]
    assert f"relayout_float32_3x4__{mw._PROCESS_TOKEN}" in move.as_text()  # says what it relays


def test_a_program_that_must_not_persist_compiles_under_its_own_name():
    def step(params, cache, batch):
        return {"y": params * batch["x"]}, {"k": cache["k"] + 1.0}

    def run(prog):
        out, cache = prog(
            jnp.full((8,), 3.0), {"k": jnp.zeros((8,))}, {"x": jnp.ones((8,))}
        )
        np.testing.assert_array_equal(np.asarray(out["y"]), np.full((8,), 3.0))
        np.testing.assert_array_equal(np.asarray(cache["k"]), np.ones((8,)))
        return prog._compiled.as_text()

    kept_out = mw._AutoLayoutProgram(
        step, dict(donate_argnums=(1,)), label="toy[8]", persist=False
    )
    assert mw._PROCESS_TOKEN in run(kept_out)  # no cache entry has this name
    cached = mw._AutoLayoutProgram(step, dict(donate_argnums=(1,)), label="toy[8]")
    assert mw._PROCESS_TOKEN not in run(cached)


def test_a_program_kept_out_of_the_cache_is_named_after_its_label():
    """The XLA module of a ``persist=False`` program reads
    ``jit_<sanitised label>__<process token>_<n>`` in a profiler trace: a
    reader recognises it, and no two draws (so no cache entry) share it."""
    def step(params, cache, batch):
        return {"y": params * batch["x"]}, {"k": cache["k"] + 1.0}

    prog = mw._AutoLayoutProgram(
        step, dict(donate_argnums=(1,)), label="token_generation_model[k4,4096]",
        persist=False,
    )
    args = (
        jax.ShapeDtypeStruct((8,), jnp.float32),
        {"k": jax.ShapeDtypeStruct((8,), jnp.float32)},
        {"x": jax.ShapeDtypeStruct((8,), jnp.float32)},
    )
    names = []
    for _ in range(2):
        text = prog.compile(*args).as_text()
        names.append(prog.jitted.__name__)
        assert f"HloModule jit_{names[-1]}" in text
    head = f"token_generation_model_k4_4096__{mw._PROCESS_TOKEN}_"
    assert all(n.startswith(head) for n in names) and names[0] != names[1]
    assert mw._sanitised("mixed_model[128]") == "mixed_model_128"


@pytest.mark.parametrize("persist", [False, True])
def test_a_program_kept_out_of_the_cache_keeps_its_name_stack(persist):
    """``enable_persistent_cache`` lowers without whole tracebacks (a cached
    program's key must not depend on its caller), and JAX then drops the name
    stack too. A ``persist=False`` program has no key to keep stable: its
    ``jax.named_scope`` regions (and its kernels' names) reach the HLO."""
    def step(params, cache, batch):
        with jax.named_scope("attn.qkv"):
            y = params * batch["x"]
        return {"y": y}, {"k": cache["k"] + 1.0}

    args = (
        jax.ShapeDtypeStruct((8,), jnp.float32),
        {"k": jax.ShapeDtypeStruct((8,), jnp.float32)},
        {"x": jax.ShapeDtypeStruct((8,), jnp.float32)},
    )
    was = jax.config.jax_include_full_tracebacks_in_locations
    jax.config.update("jax_include_full_tracebacks_in_locations", False)
    try:
        prog = mw._AutoLayoutProgram(
            step, dict(donate_argnums=(1,)), label="toy[8]", persist=persist
        )
        text = prog.compile(*args).as_text()
        assert jax.config.jax_include_full_tracebacks_in_locations is False  # put back
    finally:
        jax.config.update("jax_include_full_tracebacks_in_locations", was)
    assert ("/attn.qkv/mul" in text) is (not persist)


@pytest.mark.parametrize("paged", [False, True])
def test_only_block_layout_programs_stay_out_of_the_cache(paged):
    from nxdi_tpu.cli.lint import build_reference_app
    from nxdi_tpu.config import OnDeviceSamplingConfig

    kwargs = dict(
        tp_degree=1, batch_size=1, seq_len=64, max_context_length=32,
        dtype="bfloat16", on_device_sampling_config=OnDeviceSamplingConfig(),
        skip_warmup=True,
    )
    if paged:
        kwargs.update(is_block_kv_layout=True, pa_block_size=8, pa_num_blocks=16)
    app = build_reference_app(kwargs)
    app._build_wrappers()
    progs = [p for w in app.models.values() for p in w._programs.values()]
    assert progs and all(p.persist is (not paged) for p in progs)
