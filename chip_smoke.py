#!/usr/bin/env python
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

One process, one TPU chip. Drives the main path once through the entry points
a user calls, at the full width AND depth of Llama-3.2-1B (published
config.json; random bf16 weights made from ``--seed``):

  HF-format checkpoint on disk -> ``TpuModelForCausalLM.compile()/load()``
  -> static ``HuggingFaceGenerationAdapter.generate`` (flash prefill kernel)
  -> paged ``InferenceEngine`` + ``ReplicaIngest`` over localhost HTTP
     (flash prefill + paged decode kernels), streamed tokens,

and checks what comes out by the repo's own means: prompt logits against HF
``transformers`` fp32 on the CPU under ``check_accuracy_logits``; every
HTTP-served greedy stream equal to the static app's, or parting from it only
where ``check_replay_consistency`` calls the two candidates a near-tie.

``--chips 4`` runs ONLY the tensor-parallel path and what it is compared with
(Llama-3.1-8B widths: a 4-layer cut at tp=1 vs tp=4, then all 32 layers at
tp=4 through engine + ingest).

Standard output: progress on earlier lines; the LAST line is exactly

  {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

built by :func:`final_line` on the success and the failure path alike and
written after every server and thread is shut down. Exit code 0 only when
every phase passed; without a TPU the run fails before building anything.
"""

from __future__ import annotations

import argparse
import gc
import json
import logging
import os
import shutil
import struct
import sys
import tempfile
import threading
import time
import traceback
from concurrent.futures import ThreadPoolExecutor

# ---------------------------------------------------------------------------
# the contract's last line
# ---------------------------------------------------------------------------

NO_DEVICE = {"platform": None, "kind": None, "count": 0}


def final_line(ok: bool, device: dict) -> str:
    """THE last line of standard output — success and failure alike. Exactly
    the keys ``ok`` and ``device``; ``device`` exactly ``platform``, ``kind``,
    ``count`` as the live backend reports them."""
    return json.dumps({
        "ok": bool(ok),
        "device": {
            "platform": device["platform"],
            "kind": device["kind"],
            "count": device["count"],
        },
    })


def live_device() -> dict:
    import jax

    devs = jax.devices()
    return {
        "platform": devs[0].platform,
        "kind": devs[0].device_kind,
        "count": len(devs),
    }


class Report:
    """This script's own handle on the process's standard output. Everything
    else that writes to descriptor 1 while the run lasts — a library's
    ``print``, a runtime's C++ log — is sent to standard error, so nothing
    but :meth:`say` lines can come before the final line and nothing at all
    after it."""

    def __init__(self):
        sys.stdout.flush()
        self._out = os.fdopen(os.dup(1), "w")
        os.dup2(2, 1)
        self.t0 = time.perf_counter()

    def say(self, text: str) -> None:
        self._out.write(f"[smoke {time.perf_counter() - self.t0:7.1f}s] {text}\n")
        self._out.flush()

    def finish(self, ok: bool, device: dict) -> None:
        self._out.write(final_line(ok, device) + "\n")
        self._out.flush()
        self._out.close()


# ---------------------------------------------------------------------------
# models: published config.json values
# ---------------------------------------------------------------------------

#: meta-llama/Llama-3.2-1B config.json (hidden 2048, 16 layers, 32/8 heads,
#: head_dim 64, intermediate 8192, vocab 128256, tied embeddings, llama3 rope)
LLAMA_3_2_1B = {
    "architectures": ["LlamaForCausalLM"],
    "model_type": "llama",
    "hidden_size": 2048,
    "intermediate_size": 8192,
    "num_hidden_layers": 16,
    "num_attention_heads": 32,
    "num_key_value_heads": 8,
    "head_dim": 64,
    "vocab_size": 128256,
    "max_position_embeddings": 131072,
    "rms_norm_eps": 1e-5,
    "rope_theta": 500000.0,
    "rope_scaling": {
        "factor": 32.0,
        "high_freq_factor": 4.0,
        "low_freq_factor": 1.0,
        "original_max_position_embeddings": 8192,
        "rope_type": "llama3",
    },
    "hidden_act": "silu",
    "attention_bias": False,
    "mlp_bias": False,
    "tie_word_embeddings": True,
    "bos_token_id": 128000,
    "eos_token_id": 128001,
    "torch_dtype": "bfloat16",
}

#: meta-llama/Llama-3.1-8B config.json (hidden 4096, 32 layers, 32/8 heads,
#: head_dim 128, intermediate 14336, vocab 128256, untied, llama3 rope)
LLAMA_3_1_8B = dict(
    LLAMA_3_2_1B,
    hidden_size=4096,
    intermediate_size=14336,
    num_hidden_layers=32,
    head_dim=128,
    rope_scaling=dict(LLAMA_3_2_1B["rope_scaling"], factor=8.0),
    tie_word_embeddings=False,
)

SEQ_LEN = 2048  # decode window
PROMPT_BUCKET = 1024  # largest prompt bucket
SHORT_BUCKET = 128  # bucket of the logit-matched short prompt
SLOTS = 8  # decode rows of the serving stack
PA_BLOCK = 128
STATIC_BATCH = 4
PROMPT_LENGTHS = (24, 200, 333, 600, 900)  # the first is the logit-matched one
WEIGHT_STD = 0.02

# bf16-vs-fp32 tolerances, fixed before the first chip run (PERF.md, PR 22).
# LOGIT_TOL bounds max |logit - fp32 reference| per position over the 128256
# logits: bf16 through 16 layers puts ~0.1 of rounding noise there (logits
# spread ~0.9, so a wrong mask, rope or weight mapping is off by >= 1).
# NEAR_TIE_TOL is the logit gap under which two candidate tokens count as a
# near-tie between two bf16 kernels (~4 sigma of the noise on one pair of
# logits; top-1 vs an arbitrary wrong token is 2-4 apart).
LOGIT_TOL = 0.25
NEAR_TIE_TOL = 0.125


def hf_tensor_shapes(cfg: dict):
    """``(name, shape, std)`` of every tensor of an HF llama checkpoint, in
    file order; std None = a norm weight (ones)."""
    h, inter = cfg["hidden_size"], cfg["intermediate_size"]
    q = cfg["num_attention_heads"] * cfg["head_dim"]
    kv = cfg["num_key_value_heads"] * cfg["head_dim"]
    out = [("model.embed_tokens.weight", (cfg["vocab_size"], h), WEIGHT_STD)]
    for i in range(cfg["num_hidden_layers"]):
        p = f"model.layers.{i}."
        out += [
            (p + "input_layernorm.weight", (h,), None),
            (p + "self_attn.q_proj.weight", (q, h), WEIGHT_STD),
            (p + "self_attn.k_proj.weight", (kv, h), WEIGHT_STD),
            (p + "self_attn.v_proj.weight", (kv, h), WEIGHT_STD),
            (p + "self_attn.o_proj.weight", (h, q), WEIGHT_STD),
            (p + "post_attention_layernorm.weight", (h,), None),
            (p + "mlp.gate_proj.weight", (inter, h), WEIGHT_STD),
            (p + "mlp.up_proj.weight", (inter, h), WEIGHT_STD),
            (p + "mlp.down_proj.weight", (h, inter), WEIGHT_STD),
        ]
    out.append(("model.norm.weight", (h,), None))
    if not cfg["tie_word_embeddings"]:
        out.append(("lm_head.weight", (cfg["vocab_size"], h), WEIGHT_STD))
    return out


def random_bf16(seed: int, index: int, shape, std):
    """One tensor from ``(seed, index)`` — independent of what else is made,
    in which order, or on which thread."""
    import ml_dtypes
    import numpy as np

    if std is None:
        return np.ones(shape, dtype=ml_dtypes.bfloat16)
    rng = np.random.default_rng([seed, index])
    x = rng.standard_normal(shape, dtype=np.float32)
    x *= std
    return x.astype(ml_dtypes.bfloat16)


def write_checkpoint(path: str, cfg: dict, seed: int) -> int:
    """HF-format checkpoint directory: ``config.json`` + one
    ``model.safetensors`` written tensor by tensor (the header first, then
    each tensor's bytes as it is made, a layer's worth in flight — the host
    never holds the model).
    Returns the bytes of weights written."""
    import numpy as np

    os.makedirs(path, exist_ok=True)
    with open(os.path.join(path, "config.json"), "w") as f:
        json.dump(cfg, f, indent=1)
    tensors = hf_tensor_shapes(cfg)
    header, offset = {"__metadata__": {"format": "pt"}}, 0
    for name, shape, _ in tensors:
        n = 2 * int(np.prod(shape))
        header[name] = {
            "dtype": "BF16", "shape": list(shape),
            "data_offsets": [offset, offset + n],
        }
        offset += n
    blob = json.dumps(header, separators=(",", ":")).encode()
    blob += b" " * (-len(blob) % 8)
    jobs = [(i, shape, std) for i, (_, shape, std) in enumerate(tensors)]
    with open(os.path.join(path, "model.safetensors"), "wb") as f, \
            ThreadPoolExecutor(max_workers=8) as pool:
        f.write(struct.pack("<Q", len(blob)))
        f.write(blob)
        for lo in range(0, len(jobs), 9):  # a layer's worth in flight
            made = pool.map(lambda j: random_bf16(seed, *j), jobs[lo : lo + 9])
            for arr in made:
                f.write(arr.tobytes())
    return offset


# ---------------------------------------------------------------------------
# applications, built the way cli/inference_demo.py builds them
# ---------------------------------------------------------------------------

def static_tpu_config(**overrides):
    """Contiguous-KV app: ``HuggingFaceGenerationAdapter.generate``'s path."""
    from nxdi_tpu.config import OnDeviceSamplingConfig, TpuConfig

    kwargs = dict(
        tp_degree=1,
        batch_size=STATIC_BATCH,
        seq_len=SEQ_LEN,
        max_context_length=PROMPT_BUCKET,
        context_encoding_buckets=[SHORT_BUCKET, PROMPT_BUCKET],
        dtype="bfloat16",
        on_device_sampling_config=OnDeviceSamplingConfig(),
        attn_kernel_enabled=True,  # Pallas flash prefill
    )
    kwargs.update(overrides)
    return TpuConfig(**kwargs)


def paged_tpu_config(**overrides):
    """The serving stack ``bench.py`` builds (paged KV, block 128, 8 slots,
    window 2048) with the attention kernels switched ON: flash prefill and
    the block-table paged decode kernel."""
    from nxdi_tpu.config import OnDeviceSamplingConfig, TpuConfig

    kwargs = dict(
        tp_degree=1,
        batch_size=SLOTS,
        ctx_batch_size=1,
        tkg_batch_size=SLOTS,
        seq_len=SEQ_LEN,
        max_context_length=PROMPT_BUCKET,
        dtype="bfloat16",
        on_device_sampling_config=OnDeviceSamplingConfig(),
        is_block_kv_layout=True,
        pa_block_size=PA_BLOCK,
        # every slot can hold a full window plus one block of headroom for
        # the admission watermark
        pa_num_blocks=SLOTS * (SEQ_LEN // PA_BLOCK) + SLOTS,
        attn_kernel_enabled=True,
        attn_block_tkg_kernel_enabled=True,
        telemetry={"detail": "basic"},
    )
    kwargs.update(overrides)
    return TpuConfig(**kwargs)


def build_app(model_path: str, tpu_config, app_cls=None):
    """``(app, config)`` for a llama checkpoint directory — the construction
    of ``cli/inference_demo.py run_inference``."""
    from nxdi_tpu.generation.hf_adapter import load_pretrained_config
    from nxdi_tpu.models.registry import get_family
    from nxdi_tpu.runtime.application import TpuModelForCausalLM

    family, cfg_cls = get_family("llama")
    config = cfg_cls(tpu_config, load_config=load_pretrained_config(model_path))
    cls = app_cls or getattr(family, "APPLICATION_CLS", TpuModelForCausalLM)
    return cls(model_path, config, model_family=family)


def say_programs(say, app) -> dict:
    """Print every compiled program's attention strategies and the KV-cache
    memory layouts it resolved (AUTO layouts: programs that disagree pay a
    cache relayout at every hand-over); returns ``{label: strategies}``."""
    import jax

    strategies = {}
    for wrapper in app.models.values():
        for prog in wrapper._programs.values():
            strategies[prog.label] = list(prog.attention_strategies)
            layouts = sorted(
                {str(f.layout) for f in jax.tree_util.tree_leaves(prog._cache_formats)}
            )
            say(f"  {prog.label}: {','.join(prog.attention_strategies)}; "
                f"cache layout {layouts}")
    return strategies


def require_strategy(strategies: dict, label_prefix: str, name: str) -> None:
    hits = [k for k, v in strategies.items() if k.startswith(label_prefix)]
    if not hits:
        raise AssertionError(f"no program {label_prefix}* among {sorted(strategies)}")
    for label in hits:
        if name not in strategies[label]:
            raise AssertionError(
                f"{label}: kernel strategy {name!r} did not engage "
                f"(chose {strategies[label]})"
            )


class CacheEvents:
    """Persistent-compilation-cache hits and misses, from ``jax.monitoring``."""

    def __init__(self):
        import jax

        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_listener(self._on_event)
        # standard error names every entry written to or served from the cache
        for name in ("jax._src.compiler", "jax._src.compilation_cache"):
            logger = logging.getLogger(name)
            logger.setLevel(logging.DEBUG)
            logger.addFilter(
                lambda r: r.levelno > logging.DEBUG
                or "ersistent compilation cache" in r.getMessage()
            )

    def _on_event(self, event: str, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snapshot(self):
        return self.hits, self.misses


def memory_line(tag: str) -> str:
    import jax

    parts = []
    for d in jax.devices():
        s = d.memory_stats() or {}
        parts.append(
            f"dev{d.id} in_use={s.get('bytes_in_use', 0) / 2**30:.2f}GiB "
            f"peak={s.get('peak_bytes_in_use', 0) / 2**30:.2f}GiB"
        )
    return f"memory[{tag}]: " + "; ".join(parts)


def make_prompts(seed: int, lengths, vocab: int):
    import numpy as np

    rng = np.random.default_rng([seed, 10_000])
    # token 0 is the adapter's pad id: prompts never contain it
    return [rng.integers(1, vocab, size=n).astype(np.int64).tolist() for n in lengths]


def static_greedy(app, prompts, max_new: int):
    """Greedy streams of ``prompts`` through the static adapter, generated
    in batches of the compiled batch size; returns one token list each."""
    import numpy as np

    from nxdi_tpu.generation.hf_adapter import HuggingFaceGenerationAdapter

    adapter = HuggingFaceGenerationAdapter(app)
    b = app.tpu_config.batch_size
    streams = []
    for lo in range(0, len(prompts), b):
        rows = prompts[lo : lo + b]
        width = max(len(r) for r in rows)
        ids = np.zeros((len(rows), width), dtype=np.int64)
        for i, r in enumerate(rows):
            ids[i, : len(r)] = r
        out = np.asarray(adapter.generate(ids, max_new_tokens=max_new))
        for i, r in enumerate(rows):
            streams.append(out[i, len(r) : len(r) + max_new].tolist())
    return streams


def serve_over_http(engine, prompts, max_new: int, gap_s: float, deadline_s: float):
    """``ReplicaIngest.serve(port=0)`` + one client thread per request:
    POST /submit at its arrival time, then GET /stream by cursor until done.
    Returns the final stream records; servers and threads are shut down
    before it returns, whatever happens."""
    from nxdi_tpu.router import ReplicaIngest, http_json

    ingest = ReplicaIngest(engine)
    server = ingest.serve(port=0)
    results, errors = [None] * len(prompts), []

    def client(i):
        try:
            time.sleep(i * gap_s)
            rid = f"smoke-{i}"
            status, resp = http_json(
                "POST", f"{server.url}/submit",
                {"request_id": rid, "prompt": prompts[i], "max_new_tokens": max_new},
                10.0,
            )
            if status != 200:
                raise RuntimeError(f"/submit {rid} -> {status} {resp}")
            cursor, tokens = 0, []
            t_end = time.time() + deadline_s
            while time.time() < t_end:
                status, resp = http_json(
                    "GET", f"{server.url}/stream?request_id={rid}&cursor={cursor}",
                    None, 10.0,
                )
                if status != 200:
                    raise RuntimeError(f"/stream {rid} -> {status} {resp}")
                cursor = resp["cursor"]
                tokens.extend(resp["tokens"])
                if resp["done"]:
                    results[i] = dict(resp, tokens=tokens)
                    return
                time.sleep(0.01)
            raise TimeoutError(f"{rid} not done after {deadline_s}s")
        except BaseException as e:  # re-raised by the caller below
            errors.append(e)

    threads = [
        threading.Thread(target=client, args=(i,), name=f"smoke-client-{i}", daemon=True)
        for i in range(len(prompts))
    ]
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(deadline_s + len(prompts) * gap_s + 30.0)
    finally:
        ingest.stop()  # joins the driver thread, shuts the HTTP server down
    if errors:
        raise errors[0]
    if any(r is None for r in results):
        raise TimeoutError("a client thread never returned")
    return results


def compare_streams(say, reference_app, prompts, want, got, tol: float) -> None:
    """Every served stream finished without an error and equals the
    reference greedy stream, or parts from it at a position where the
    reference app's own teacher-forced logits put the served token within
    ``tol`` of the top — a near-tie two bf16 kernels may round apart. A
    parting that is not a near-tie raises."""
    from nxdi_tpu.utils.accuracy import check_replay_consistency

    for i, (prompt, ref, rec) in enumerate(zip(prompts, want, got)):
        if rec["finish_reason"] not in ("length", "eos") or rec["error"]:
            raise AssertionError(f"stream {i} finished {rec['finish_reason']}: {rec['error']}")
        toks = rec["tokens"]
        if len(toks) != len(ref):
            raise AssertionError(f"stream {i}: {len(toks)} tokens, expected {len(ref)}")
        if toks == ref:
            say(f"stream {i} (prompt {len(prompt)}): {len(toks)} tokens, identical")
            continue
        j = next(k for k in range(len(ref)) if toks[k] != ref[k])
        report = check_replay_consistency(
            reference_app, prompt + toks[: j + 1], len(prompt),
            divergence_difference_tol=tol,
        )
        gap = report["errors_by_index"][j]
        say(
            f"stream {i} (prompt {len(prompt)}): parts at token {j} "
            f"(served {toks[j]}, reference {ref[j]}), logit gap {gap:.4f}"
        )
        if not report["match"]:
            raise AssertionError(
                f"stream {i} parts from the reference at token {j} with a "
                f"logit gap of {gap:.4f} > {tol}: not a near-tie"
            )


def concurrency_seen(engine):
    """(most decode rows in one step, prefills that ran while another
    request held a slot) from the engine's flight records."""
    most, overlapped = 0, 0
    for rec in engine.flight.snapshot_records():
        if rec.decode:
            most = max(most, len(rec.decode["rows"]))
        if rec.prefills and rec.slots_busy > len(rec.prefills):
            overlapped += 1
    return most, overlapped


# ---------------------------------------------------------------------------
# the one-chip run
# ---------------------------------------------------------------------------

def run_one_chip(args, rep: Report, work: str) -> None:
    import jax
    import numpy as np

    from nxdi_tpu.runtime.application import enable_persistent_cache
    from nxdi_tpu.serving import InferenceEngine, SchedulerConfig
    from nxdi_tpu.utils.accuracy import check_accuracy_logits, hf_forward_logits

    say = rep.say
    cache_dir = enable_persistent_cache()
    say(f"compile cache: {cache_dir} (JAX_COMPILATION_CACHE_DIR "
        f"{'set' if os.environ.get('JAX_COMPILATION_CACHE_DIR') else 'unset'})")
    events = CacheEvents()
    cfg = dict(LLAMA_3_2_1B)
    if args.layers:
        cfg["num_hidden_layers"] = args.layers

    t = time.perf_counter()
    ckpt = os.path.join(work, "llama-3.2-1b")
    n_bytes = write_checkpoint(ckpt, cfg, args.seed)
    say(f"checkpoint: {n_bytes / 2**30:.2f} GiB bf16, {cfg['num_hidden_layers']} "
        f"layers -> {ckpt} in {time.perf_counter() - t:.1f}s")

    prompts = make_prompts(args.seed, PROMPT_LENGTHS, cfg["vocab_size"])
    short = np.asarray([prompts[0]], dtype=np.int64)

    # -- the plain reference: HF transformers, fp32, on the CPU --------------
    t = time.perf_counter()
    import torch
    from transformers import AutoModelForCausalLM

    hf = AutoModelForCausalLM.from_pretrained(ckpt, torch_dtype=torch.float32).eval()
    golden = hf_forward_logits(hf, short)
    del hf
    say(f"reference: HF fp32 CPU logits {golden.shape} of the {short.shape[1]}-token "
        f"prompt in {time.perf_counter() - t:.1f}s")

    # -- static path ---------------------------------------------------------
    t = time.perf_counter()
    art_static = os.path.join(work, "compiled-static")
    app = build_app(ckpt, static_tpu_config())
    app.compile(art_static)
    cold_static = time.perf_counter() - t
    hits0, miss0 = events.snapshot()
    t = time.perf_counter()
    app.load(art_static)
    hits1, miss1 = events.snapshot()
    say(f"static app: cold compile {cold_static:.1f}s (cache hits {hits0}, misses {miss0}); "
        f"load + warmup {time.perf_counter() - t:.1f}s (hits {hits1 - hits0}, "
        f"misses {miss1 - miss0})")
    strategies = say_programs(say, app)
    require_strategy(strategies, "context_encoding_model[", "cte_flash_kernel")
    say(memory_line("static loaded"))

    t = time.perf_counter()
    errs = check_accuracy_logits(
        app, short, golden_logits=golden, divergence_difference_tol=LOGIT_TOL
    )
    say(f"logits vs fp32 reference: max |err| {max(errs.values()):.4f} over "
        f"{len(errs)} positions (tolerance {LOGIT_TOL}) in {time.perf_counter() - t:.1f}s")

    t = time.perf_counter()
    want = static_greedy(app, prompts, args.max_new)
    flat = np.asarray(want)
    if not ((flat >= 0) & (flat < cfg["vocab_size"])).all():
        raise AssertionError("static generate produced a token outside the vocabulary")
    say(f"static generate: {len(prompts)} prompts x {args.max_new} greedy tokens in "
        f"{time.perf_counter() - t:.1f}s; first stream {want[0][:8]}...")

    # -- the same app loaded a second time: served from the compile cache ----
    hits1, miss1 = events.snapshot()
    t = time.perf_counter()
    again = build_app(ckpt, static_tpu_config())
    again.load(art_static)
    hits2, miss2 = events.snapshot()
    n_programs = len(strategies)
    say(f"second load of the static app: {time.perf_counter() - t:.1f}s, persistent "
        f"cache hits {hits2 - hits1}, misses {miss2 - miss1} ({n_programs} step programs)")
    if hits2 - hits1 < n_programs:
        raise AssertionError(
            f"second load hit the compile cache {hits2 - hits1} times for "
            f"{n_programs} step programs — the cache in {cache_dir} is not serving"
        )
    del again
    gc.collect()

    # -- serving path --------------------------------------------------------
    t = time.perf_counter()
    art_paged = os.path.join(work, "compiled-paged")
    paged = build_app(ckpt, paged_tpu_config())
    paged.compile(art_paged)
    cold_paged = time.perf_counter() - t
    t = time.perf_counter()
    paged.load(art_paged)
    say(f"paged app: cold compile {cold_paged:.1f}s; load + warmup "
        f"{time.perf_counter() - t:.1f}s")
    strategies = say_programs(say, paged)
    require_strategy(strategies, "context_encoding_model[", "cte_flash_kernel")
    require_strategy(strategies, "token_generation_model[", "tkg_paged_kernel")
    say(memory_line("static + paged loaded"))

    t = time.perf_counter()
    engine = InferenceEngine(paged, SchedulerConfig(num_slots=SLOTS))
    got = serve_over_http(engine, prompts, args.max_new, gap_s=0.05, deadline_s=300.0)
    most, overlapped = concurrency_seen(engine)
    say(f"served {len(got)} requests over HTTP in {time.perf_counter() - t:.1f}s; up to "
        f"{most} rows decoded together, {overlapped} prefills ran beside a running request")
    if most < 2 or overlapped < 1:
        raise AssertionError("no request arrived while another was decoding")
    say(memory_line("served"))
    del engine, paged  # a parting is judged by the static app's logit probe
    gc.collect()
    compare_streams(say, app, prompts, want, got, NEAR_TIE_TOL)
    peak = (jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use")
    say(f"peak HBM over the run: {peak} bytes")


# ---------------------------------------------------------------------------
# the four-chip run: tensor parallelism, and nothing else
# ---------------------------------------------------------------------------

def random_params(struct, seed: int):
    """Random bf16 weights in the app's own layout, one tensor at a time in
    its final dtype (the host never holds a second copy). Leaf ``i``, layer
    ``l`` depends on ``(seed, i, l)`` only, so a 4-layer cut is the first
    four layers of the full model."""
    import jax
    import ml_dtypes
    import numpy as np

    leaves, treedef = jax.tree_util.tree_flatten_with_path(struct)

    def fill(item):
        i, (path, s) = item
        keys = [getattr(p, "key", None) for p in path]
        if any(k and k.endswith("norm") for k in keys):
            return np.ones(s.shape, dtype=ml_dtypes.bfloat16)
        if "layers" not in keys:
            return random_bf16(seed, 1000 * i, s.shape, WEIGHT_STD)
        out = np.empty(s.shape, dtype=ml_dtypes.bfloat16)
        for layer in range(s.shape[0]):
            out[layer] = random_bf16(seed, 1000 * i + layer + 1, s.shape[1:], WEIGHT_STD)
        return out

    with ThreadPoolExecutor(max_workers=8) as pool:
        filled = list(pool.map(fill, enumerate(leaves)))
    return jax.tree_util.tree_unflatten(treedef, filled)


def build_random_app(cfg: dict, tpu_config, seed: int, work: str, name: str):
    """A llama app whose weights are drawn in memory (16 GB of 8B weights
    are not written to disk and read back); config.json still goes through
    the checkpoint directory like any model's."""
    import jax

    from nxdi_tpu.runtime.application import TpuModelForCausalLM, params_shape_struct

    path = os.path.join(work, name)
    os.makedirs(path, exist_ok=True)
    with open(os.path.join(path, "config.json"), "w") as f:
        json.dump(cfg, f, indent=1)

    class RandomWeightsApp(TpuModelForCausalLM):
        def build_params(self):
            arch = self.family.build_arch(self.config)
            struct = params_shape_struct(self.family, self.config, arch)
            # same seed = same model only while the layout does not depend on
            # tp (no KV-head replication, no vocab padding): true of 8 KV
            # heads and a 128256 vocabulary at tp=4, checked by the caller
            self.param_shapes = jax.tree_util.tree_map(lambda s: s.shape, struct)
            return random_params(struct, seed)

    return build_app(path, tpu_config, app_cls=RandomWeightsApp)


def device_bytes_in_use():
    import jax

    return [(d.memory_stats() or {}).get("bytes_in_use", 0) for d in jax.devices()]


def run_four_chips(args, rep: Report, work: str) -> None:
    import jax
    import numpy as np

    from nxdi_tpu.analysis import hlo as hlo_views
    from nxdi_tpu.runtime.application import enable_persistent_cache
    from nxdi_tpu.runtime.model_wrapper import TAG_TOKEN_GENERATION
    from nxdi_tpu.serving import InferenceEngine, SchedulerConfig
    from nxdi_tpu.utils.accuracy import probe_all_logits

    say = rep.say
    say(f"compile cache: {enable_persistent_cache()}")
    cfg = dict(LLAMA_3_1_8B)
    cut = dict(cfg, num_hidden_layers=args.cut_layers)
    if args.layers:
        cfg["num_hidden_layers"] = args.layers
    prompts = make_prompts(args.seed, PROMPT_LENGTHS, cfg["vocab_size"])
    short = np.asarray([prompts[0]], dtype=np.int64)

    # (i) the same 4-layer cut at tp=1 on device 0 and at tp=4
    runs = {}
    for tp in (1, 4):
        t = time.perf_counter()
        app = build_random_app(
            cut, static_tpu_config(tp_degree=tp), args.seed, work, f"llama-3.1-8b-cut-tp{tp}"
        )
        app.load()
        logits = probe_all_logits(app, short)[0]
        streams = static_greedy(app, prompts[:STATIC_BATCH], args.max_new)
        runs[tp] = (logits, streams, app.param_shapes)
        say(f"8B-width {cut['num_hidden_layers']}-layer cut at tp={tp}: built, loaded, "
            f"probed and generated in {time.perf_counter() - t:.1f}s; in use per device "
            f"{[f'{b / 2**30:.2f}' for b in device_bytes_in_use()]} GiB")
        say_programs(say, app)
        if tp == 1:
            del app  # frees device 0 before the same weights go up sharded
            gc.collect()
    if runs[1][2] != runs[4][2]:
        raise AssertionError("the parameter layout depends on tp: the two runs "
                             "did not draw the same model")
    err = float(np.abs(runs[1][0] - runs[4][0]).max())
    say(f"tp=1 vs tp=4 prompt logits: max |diff| {err:.4f} (tolerance {LOGIT_TOL})")
    if not np.isfinite(runs[4][0]).all() or err > LOGIT_TOL:
        raise AssertionError(f"tp=4 logits differ from tp=1 by {err}")
    compare_streams(
        say, app, prompts[:STATIC_BATCH], runs[1][1],
        [{"tokens": s, "finish_reason": "length", "error": None} for s in runs[4][1]],
        NEAR_TIE_TOL,
    )
    del app, runs
    gc.collect()

    # (ii) all 32 layers at tp=4 through engine + ingest
    t = time.perf_counter()
    paged = build_random_app(
        cfg, paged_tpu_config(tp_degree=4), args.seed, work, "llama-3.1-8b-tp4"
    )
    paged.load()
    say(f"8B {cfg['num_hidden_layers']}-layer paged app at tp=4: built + loaded + warmed "
        f"in {time.perf_counter() - t:.1f}s")
    say_programs(say, paged)
    in_use = device_bytes_in_use()
    say("bytes_in_use per device: " + ", ".join(f"{b / 2**30:.2f} GiB" for b in in_use))
    if max(in_use) > sum(in_use) / 3:
        raise AssertionError(f"device memory is not balanced across the mesh: {in_use}")
    tkg = paged.models[TAG_TOKEN_GENERATION]._programs[SEQ_LEN]
    counts = hlo_views.collective_counts(tkg._compiled.as_text())
    say(f"TKG program collectives: { {k: v for k, v in counts.items() if v} }")
    if not counts["all-reduce"]:
        raise AssertionError("the tp=4 TKG program holds no all-reduce")

    t = time.perf_counter()
    engine = InferenceEngine(paged, SchedulerConfig(num_slots=SLOTS))
    got = serve_over_http(engine, prompts, args.max_new, gap_s=0.1, deadline_s=600.0)
    most, overlapped = concurrency_seen(engine)
    say(f"served {len(got)} requests over HTTP at tp=4 in {time.perf_counter() - t:.1f}s; "
        f"up to {most} rows decoded together, {overlapped} overlapped prefills")
    vocab = cfg["vocab_size"]
    for i, rec in enumerate(got):
        ok = (
            rec["finish_reason"] == "length" and not rec["error"]
            and len(rec["tokens"]) == args.max_new
            and all(0 <= tok < vocab for tok in rec["tokens"])
        )
        say(f"  stream {i}: {rec['finish_reason']}, {len(rec['tokens'])} tokens, "
            f"first {rec['tokens'][:6]}")
        if not ok:
            raise AssertionError(f"stream {i} did not finish cleanly: {rec}")
    say(memory_line("end"))


# ---------------------------------------------------------------------------

def parse_args(argv):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--seed", type=int, default=0, help="seed of the random weights and prompts")
    p.add_argument("--chips", type=int, default=1, choices=(1, 4),
                   help="4 = run ONLY the tensor-parallel path on four chips")
    p.add_argument("--max-new", type=int, default=32, help="greedy tokens per request")
    p.add_argument("--layers", type=int, default=None,
                   help="cut the depth (fault-finding only; the default is the full depth)")
    p.add_argument("--cut-layers", type=int, default=4,
                   help="--chips 4: depth of the tp=1 vs tp=4 comparison")
    return p.parse_args(argv)


def main(argv=None) -> int:
    args = parse_args(argv)
    rep = Report()
    device, ok, work = NO_DEVICE, False, None
    try:
        device = live_device()
        import jax
        import jaxlib

        rep.say(f"device: {device}; jax {jax.__version__}, jaxlib {jaxlib.__version__}, "
                f"libtpu {_libtpu_version()}")
        if device["platform"] != "tpu":
            raise RuntimeError(
                f"no TPU: JAX reports platform {device['platform']!r} — this smoke "
                "runs on the chip or not at all"
            )
        if device["count"] < args.chips:
            raise RuntimeError(f"--chips {args.chips} needs {args.chips} devices, "
                               f"JAX reports {device['count']}")
        # scratch outside the checkout and outside what is copied back
        work = tempfile.mkdtemp(prefix="nxdi_chip_smoke_")
        t0 = time.perf_counter()
        (run_four_chips if args.chips == 4 else run_one_chip)(args, rep, work)
        rep.say(f"all phases passed in {time.perf_counter() - t0:.1f}s")
        ok = True
    except BaseException:
        traceback.print_exc(file=sys.stderr)
        rep.say("FAILED — traceback on standard error")
    finally:
        if work is not None:
            shutil.rmtree(work, ignore_errors=True)
    rep.finish(ok, device)
    return 0 if ok else 1


def _libtpu_version() -> str:
    from importlib import metadata

    try:
        return metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        return "not installed"


if __name__ == "__main__":
    sys.exit(main())
