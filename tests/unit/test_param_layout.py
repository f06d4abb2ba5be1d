"""The weights laid out once at load in the formats the token-generation
program reads them in (runtime/param_layout.py), on a tiny paged llama on the
CPU. The CPU compiler keeps every weight in the default layout and the pass
runs on the TPU alone, so the tests open the pass and seed what the decode
program would choose (a transposed q/k projection stack) and what each
program copies; the programs, the moves and the engine are the real ones."""

import jax
import numpy as np
import pytest
from jax.experimental.layout import Format, Layout

from nxdi_tpu.analysis.hlo import param_moves
from nxdi_tpu.config import OnDeviceSamplingConfig, TpuConfig
from nxdi_tpu.models.llama import modeling_llama as llama
from nxdi_tpu.runtime import model_wrapper as mw
from nxdi_tpu.runtime import param_layout
from nxdi_tpu.runtime.application import TpuModelForCausalLM
from nxdi_tpu.serving import InferenceEngine, SamplingParams, SchedulerConfig

PROMPTS = ([5, 9, 3, 17, 2, 8], [7, 13, 21, 4], [9, 2, 40])
TRANSPOSED = Layout(major_to_minor=(0, 2, 1))


def _paths(tree):
    return [jax.tree_util.keystr(p) for p, _ in jax.tree_util.tree_flatten_with_path(tree)[0]]


def _is_decode(compiled):
    return "token_generation_model" in compiled.as_text()[:400]


@pytest.fixture
def seeded(monkeypatch):
    """Open the pass and seed it: ``move`` names the leaves the decode program
    copies as held and would read transposed; ``copied_back`` the ones the
    other programs would copy back once they are pinned so."""
    box = {"move": ("q_proj", "k_proj"), "copied_back": ()}

    def chosen(compiled):
        out = []
        for path, f in zip(_paths(compiled.input_formats[0][0]),
                           jax.tree_util.tree_leaves(compiled.input_formats[0][0])):
            if any(m in path for m in box["move"]) and len(f.layout.major_to_minor) == 3:
                f = Format(TRANSPOSED, f.sharding)
            out.append(f)
        return out

    def copies(compiled, kept_args, dims):
        names = box["move"] if _is_decode(compiled) else box["copied_back"]
        return {i for i, p in enumerate(_paths(compiled.input_formats[0][0]))
                if any(m in p for m in names)}

    monkeypatch.setattr(param_layout, "chooses_layouts", lambda mesh: True)
    monkeypatch.setattr(param_layout, "_chosen_formats", chosen)
    monkeypatch.setattr(param_layout, "_copies", copies)
    return box


@pytest.fixture
def relayouts(monkeypatch):
    """Every ``_relayout`` call: ``(shape, donated)``."""
    calls = []
    real = mw._relayout

    def counting(a, fmt, donate=False):
        calls.append((tuple(a.shape), donate))
        return real(a, fmt, donate)

    monkeypatch.setattr(mw, "_relayout", counting)
    return calls


def build(hf, paged=True, **kw):
    model, cfg = hf
    sd = {k: v.detach().numpy() for k, v in model.state_dict().items()}
    tc = dict(tp_degree=1, seq_len=64, max_context_length=32, batch_size=2, dtype="float32",
              on_device_sampling_config=OnDeviceSamplingConfig(), telemetry="basic",
              ctx_batch_size=1, tkg_batch_size=3)
    if paged:
        tc.update(is_block_kv_layout=True, pa_block_size=4, pa_num_blocks=48)
    else:
        tc.update(is_continuous_batching=True, ctx_batch_size=1, tkg_batch_size=3, batch_size=3)
    tc.update(kw)

    class App(TpuModelForCausalLM):
        def get_state_dict(self):
            return sd

    app = App("<memory>", llama.LlamaInferenceConfig(TpuConfig(**tc), load_config=cfg.to_dict),
              model_family=llama)
    app.load()
    return app


def serve(app):
    engine = InferenceEngine(app, SchedulerConfig(num_slots=3))
    for p in PROMPTS:
        engine.add_request(p, SamplingParams(max_new_tokens=6))
    return sorted(tuple(o.token_ids) for o in engine.run())


def _param_shapes(app):
    return {tuple(a.shape) for a in jax.tree_util.tree_leaves(app.params)}


def test_load_moves_each_differing_leaf_once(tiny_hf_llama, seeded, relayouts):
    app = build(tiny_hf_llama)
    moved = app.param_layout.moved
    assert moved == ["['layers']['attn']['k_proj']['w']", "['layers']['attn']['q_proj']['w']"]
    # once each, its old buffer given up, at load
    assert sorted(relayouts) == sorted(
        (tuple(a.shape), True) for p, a in zip(_paths(app.params), jax.tree_util.tree_leaves(app.params))
        if p in moved)
    for path, a in zip(_paths(app.params), jax.tree_util.tree_leaves(app.params)):
        assert (a.format.layout.major_to_minor == (0, 2, 1)) is (path in moved), path
    # every program of the app takes them so
    for w in app.models.values():
        for prog in w._programs.values():
            assert prog.param_formats is app.param_formats
            got = [f.layout for f in jax.tree_util.tree_leaves(prog._compiled.input_formats[0][0])]
            assert got == [a.format.layout for a in jax.tree_util.tree_leaves(app.params)], prog.label
    reg = app.telemetry.registry
    assert reg.get("nxdi_param_leaves_relaid").value() == 2
    assert reg.get("nxdi_param_bytes_relaid").value() == app.param_layout.moved_bytes > 0


def test_programs_move_no_param_after_load(tiny_hf_llama, seeded, relayouts):
    """Prefills and decode steps over and over (two engines' worth): the
    cache may move at a phase change, a param never does, and the tokens are
    the ones of the app whose weights stayed as held."""
    plain = serve(build(tiny_hf_llama, skip_warmup=True))
    app = build(tiny_hf_llama)
    at_load = len(relayouts)
    shapes = _param_shapes(app)
    assert serve(app) == plain
    assert serve(app) == plain
    assert [c for c in relayouts[at_load:] if c[0] in shapes] == []


def test_a_program_built_after_load_takes_the_params_as_held(tiny_hf_llama, seeded):
    """A wrapper built outside the app after load (the logit probe, the
    benchmark's probe around the app's pool) is not pinned: it compiles for
    the layouts the params are held in."""
    from nxdi_tpu.utils.accuracy import probe_all_logits

    ids = np.array([[5, 9, 3, 17, 2, 8, 11, 42]], dtype=np.int32)
    plain = probe_all_logits(build(tiny_hf_llama, skip_warmup=True), ids)
    app = build(tiny_hf_llama)
    assert app.param_layout.moved
    np.testing.assert_allclose(probe_all_logits(app, ids), plain, rtol=1e-5, atol=1e-5)
    (probe,) = app._logit_probe[0]._programs.values()
    assert probe.param_formats is None
    assert [f.layout for f in jax.tree_util.tree_leaves(probe._compiled.input_formats[0][0])] == [
        a.format.layout for a in jax.tree_util.tree_leaves(app.params)]


def test_a_param_held_in_a_foreign_format_raises(tiny_hf_llama, seeded):
    app = build(tiny_hf_llama)
    q = app.params["layers"]["attn"]["q_proj"]["w"]
    app.params["layers"]["attn"]["q_proj"]["w"] = jax.device_put(np.asarray(q), q.sharding)
    app.params = dict(app.params)  # a new tree: the programs check it
    with pytest.raises(ValueError, match=r"q_proj.*never on a call"):
        serve(app)


def test_a_leaf_another_program_would_copy_back_stays_as_held(tiny_hf_llama, seeded, relayouts):
    seeded["copied_back"] = ("k_proj",)
    app = build(tiny_hf_llama)
    assert app.param_layout.moved == ["['layers']['attn']['q_proj']['w']"]
    assert app.param_layout.kept == ["['layers']['attn']['k_proj']['w']"]
    assert [c[0] for c in relayouts] == [tuple(app.params["layers"]["attn"]["q_proj"]["w"].shape)]
    assert app.params["layers"]["attn"]["k_proj"]["w"].format.layout.major_to_minor == (0, 1, 2)
    assert "kept as held" in param_layout.describe(app.param_layout)


def test_the_prefills_of_a_pinned_app_compile_with_their_own_options(tiny_hf_llama, seeded,
                                                                     monkeypatch):
    """On the TPU a pinned app's prefill programs take no cross-program
    prefetch (``_PREFILL_OPTIONS``); the CPU knows no such option, so the test
    hands the pass one the CPU compiler takes. The decode program keeps none,
    and every prefill compile (the pass's, a later one) takes them."""
    monkeypatch.setattr(param_layout, "_on_tpu", lambda mesh: True)
    monkeypatch.setattr(param_layout, "_PREFILL_OPTIONS", {"xla_embed_ir_in_executable": False})
    app = build(tiny_hf_llama)
    assert app.param_layout.moved
    options = {w.tag: {p.label: p.compiler_options for p in w._programs.values()}
               for w in app.models.values()}
    assert list(options[mw.TAG_CONTEXT_ENCODING].values()) == [
        {"xla_embed_ir_in_executable": False}]
    assert list(options[mw.TAG_TOKEN_GENERATION].values()) == [{}]
    before = serve(app)
    for prog in app.models[mw.TAG_CONTEXT_ENCODING]._programs.values():
        prog.pin_params(app.param_formats, prog.compiler_options)  # compiled again on its call
    assert serve(app) == before


def test_programs_from_the_persistent_cache_keep_the_default(tiny_hf_llama, seeded, relayouts):
    """A contiguous-cache app's programs may be served from the persistent
    cache, whose executables report the default layout: nothing moves."""
    app = build(tiny_hf_llama, paged=False)
    assert all(p.persist for w in app.models.values() for p in w._programs.values())
    assert app.param_layout.formats is None and app.param_layout.moved == []
    assert [c for c in relayouts if c[0] in _param_shapes(app)] == []  # the cache may move
    assert app.telemetry.registry.get("nxdi_param_leaves_relaid").value() == 0


def test_off_the_tpu_the_pass_does_not_run(tiny_hf_llama, relayouts):
    app = build(tiny_hf_llama)
    assert app.param_layout.formats is None and app.param_formats is None
    assert all(p.param_formats is None for w in app.models.values() for p in w._programs.values())
    assert [c for c in relayouts if c[0] in _param_shapes(app)] == []


# -- analysis/hlo.py param_moves on hand-written optimised HLO -----------------

_HLO = """HloModule jit_step

%fused_slice (param_0.1: bf16[4,64,32], param_1.1: s32[]) -> bf16[1,64,32] {
  %param_0.1 = bf16[4,64,32]{2,1,0} parameter(0)
  %param_1.1 = s32[] parameter(1)
  %c = s32[] constant(0)
  ROOT %dynamic_slice.1 = bf16[1,64,32]{2,1,0} dynamic-slice(%param_0.1, %param_1.1, %c, %c), dynamic_slice_sizes={1,64,32}
}

%fused_slice_t (param_0.2: bf16[4,64,32], param_1.2: s32[]) -> bf16[1,64,32] {
  %param_0.2 = bf16[4,64,32]{2,1,0} parameter(0)
  %param_1.2 = s32[] parameter(1)
  %c.2 = s32[] constant(0)
  ROOT %dynamic_slice.2 = bf16[1,64,32]{1,2,0} dynamic-slice(%param_0.2, %param_1.2, %c.2, %c.2), dynamic_slice_sizes={1,64,32}
}

%fused_dot (param_0.3: bf16[8,64], param_1.3: bf16[64,32]) -> bf16[8,32] {
  %param_0.3 = bf16[8,64]{1,0} parameter(0)
  %param_1.3 = bf16[64,32]{1,0} parameter(1)
  %copy.9 = bf16[64,32]{0,1} copy(%param_1.3)
  ROOT %dot.1 = bf16[8,32]{1,0} dot(%param_0.3, %copy.9), lhs_contracting_dims={1}, rhs_contracting_dims={0}
}

%body (arg: (s32[], bf16[4,64,32], bf16[4,64,32])) -> (s32[], bf16[4,64,32], bf16[4,64,32]) {
  %arg = (s32[], bf16[4,64,32], bf16[4,64,32]) parameter(0)
  %i = s32[] get-tuple-element(%arg), index=0
  %w = bf16[4,64,32]{2,1,0} get-tuple-element(%arg), index=1
  %slice_fusion.1 = bf16[1,64,32]{2,1,0} fusion(%w, %i), kind=kLoop, calls=%fused_slice
  %copy.1 = bf16[1,64,32]{1,2,0} copy(%slice_fusion.1)
  %u = bf16[4,64,32]{2,1,0} get-tuple-element(%arg), index=2
  %slice_fusion.2 = bf16[1,64,32]{1,2,0} fusion(%u, %i), kind=kLoop, calls=%fused_slice_t
  ROOT %t = (s32[], bf16[4,64,32], bf16[4,64,32]) tuple(%i, %w, %u)
}

ENTRY %main (p0: bf16[4,64,32], p1: bf16[4,64,32], p2: bf16[64,32], p3: bf16[8,64]) -> bf16[8,32] {
  %p0 = bf16[4,64,32]{2,1,0} parameter(0)
  %p1 = bf16[4,64,32]{2,1,0} parameter(1)
  %p2 = bf16[64,32]{1,0} parameter(2)
  %p3 = bf16[8,64]{1,0} parameter(3)
  %copy.2 = bf16[64,32]{0,1} copy(%p2)
  %conv = bf16[8,32]{1,0} fusion(%p3, %p2), kind=kOutput, calls=%fused_dot
  ROOT %dot.2 = bf16[8,32]{1,0} dot(%p3, %copy.2), lhs_contracting_dims={1}, rhs_contracting_dims={0}
}
"""


def test_param_moves_reads_copies_and_relaid_slices():
    dims = [(4, 64, 32), (4, 64, 32), (64, 32)]
    moves = {m.instruction: m for m in param_moves(_HLO, dims, kept_args=(0, 1, 2, 3))}
    # a copy of an entry parameter; a copy of a layer's slice (the stacked
    # shape is shared, so both stacks are candidates); a slice taken straight
    # into another layout. A dot fusion's own re-tiling is not a move.
    assert moves["copy.2"].what == "copy" and moves["copy.2"].leaves == (2,)
    assert moves["copy.1"].what == "copy of a layer's slice" and moves["copy.1"].leaves == (0, 1)
    assert moves["slice_fusion.2"].what == "slice into another layout"
    assert set(moves) == {"copy.1", "copy.2", "slice_fusion.2"}
    # pruned arguments shift the parameter numbers: parameter(2) is flat arg 3
    # here, the batch's, no weight
    assert "copy.2" not in {m.instruction for m in param_moves(_HLO, dims, kept_args=(0, 1, 3, 4))}
