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
    with mw._outside_the_persistent_cache() as a:
        assert jax.config.jax_persistent_cache_min_compile_time_secs == 1e9
    with pytest.raises(RuntimeError):
        with mw._outside_the_persistent_cache() as b:
            raise RuntimeError("compile failed")
    assert jax.config.jax_persistent_cache_min_compile_time_secs == was
    assert a != b and a.startswith(mw._PROCESS_TOKEN) and b.startswith(mw._PROCESS_TOKEN)


def test_relayout_is_an_identity_of_its_own_name_kept_per_format():
    x = jnp.arange(12.0).reshape(3, 4)
    before = len(mw._RELAYOUTS)
    y = mw._relayout(x, x.format)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(x))
    assert y.format == x.format
    mw._relayout(x, x.format)
    assert len(mw._RELAYOUTS) == before + 1  # the second call reused the first
    move = mw._RELAYOUTS[(x.format, x.format, x.shape, x.dtype)]
    assert f"relayout_{mw._PROCESS_TOKEN}" in move.as_text()


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
