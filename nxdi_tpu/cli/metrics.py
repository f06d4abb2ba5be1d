"""``python -m nxdi_tpu.cli.metrics`` — the serving-telemetry export surface.

Builds the tiny llama reference app (the same one ``nxdi_tpu.cli.lint``
audits, here with random weights so it can actually generate; on the attached
backend, ``--on-cpu`` for the CPU), drives a short burst of demo traffic through the paged-KV serving
path (block manager + request spans + generation dispatches), and emits the
telemetry three ways:

- Prometheus text exposition (stdout, or scrape it with ``--serve``),
- JSON snapshot (``--json FILE`` or stdout with ``--format json``),
- Chrome/Perfetto ``trace_events`` JSON of the request spans
  (``--perfetto FILE`` — load in ui.perfetto.dev or chrome://tracing).

Every snapshot (including ``--serve``'s ``/metrics.json`` and the probes'
``--metrics-out`` dumps) embeds the cost observatory's per-program
CostSheet table as ``_cost_sheets``, and the Prometheus text carries the
CostSheet-joined ``nxdi_program_mfu_pct`` / ``nxdi_program_hbm_bw_pct`` /
``nxdi_roofline_gap_ratio`` gauges — one file captures measured AND
theoretical (see ``python -m nxdi_tpu.cli.costs`` for the standalone
table).

Usage:

  # one-shot: demo traffic, Prometheus text + JSON snapshot to stdout
  python -m nxdi_tpu.cli.metrics

  # serve a /metrics endpoint for a scrape (also /metrics.json, /snapshot,
  # /healthz, /trace.json, /postmortem — the last needs a flight recorder,
  # i.e. a live serving engine on the same telemetry)
  python -m nxdi_tpu.cli.metrics --serve --port 9400

  # write the Perfetto trace of the demo requests
  python -m nxdi_tpu.cli.metrics --perfetto /tmp/requests.json
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from typing import List, Optional

from nxdi_tpu.cli import add_on_cpu_flag, use_cpu_backend

import numpy as np


def setup_metrics_parser(p: argparse.ArgumentParser) -> None:
    add_on_cpu_flag(p)
    p.add_argument("--format", choices=["prom", "json", "both"], default="both",
                   help="what to print to stdout (default: both)")
    p.add_argument("--json", dest="json_path", default=None,
                   help="also write the JSON snapshot to this file")
    p.add_argument("--perfetto", dest="perfetto_path", default=None,
                   help="write a Perfetto trace_events JSON of request spans")
    p.add_argument("--serve", action="store_true",
                   help="after the demo traffic, serve /metrics (Prometheus "
                        "text), /metrics.json and /trace.json over HTTP "
                        "until interrupted")
    p.add_argument("--host", default="127.0.0.1")
    p.add_argument("--port", type=int, default=9400)
    p.add_argument("--requests", type=int, default=2,
                   help="demo requests to run (default 2)")
    p.add_argument("--max-new-tokens", type=int, default=6)
    p.add_argument("--detail", choices=["basic", "full"], default="full",
                   help="telemetry detail level for the demo app "
                        "(full = synced dispatch latency; default)")
    p.add_argument("--contiguous", action="store_true",
                   help="drive the contiguous-KV HF-adapter path instead of "
                        "the paged block-manager serving loop (no "
                        "block-manager gauges in the output)")
    p.add_argument("-q", "--quiet", action="store_true",
                   help="suppress the stderr progress notes")


def _note(quiet: bool, msg: str) -> None:
    if not quiet:
        print(msg, file=sys.stderr, flush=True)


def build_loaded_reference_app(tpu_kwargs: dict):
    """The lint CLI's reference app, loaded with tiny random weights so it
    can generate (the program set tier-1 compiles everywhere)."""
    import jax.numpy as jnp
    import jax.tree_util as jtu
    import ml_dtypes

    from nxdi_tpu.cli.lint import build_reference_app
    from nxdi_tpu.models.llama import modeling_llama as ml
    from nxdi_tpu.runtime.application import params_shape_struct

    app = build_reference_app(tpu_kwargs)
    struct = params_shape_struct(ml, app.config, ml.build_arch(app.config))
    rng = np.random.default_rng(0)
    weights = jtu.tree_map(
        lambda s: (rng.standard_normal(s.shape) * 0.02).astype(
            ml_dtypes.bfloat16 if s.dtype == jnp.bfloat16 else s.dtype
        ),
        struct,
    )
    app.build_params = lambda: weights
    app.load()
    return app


def run_paged_demo(app, n_requests: int, max_new_tokens: int) -> None:
    """A miniature serving loop over the paged layout: per request — span
    start, block allocation ("pad" phase), prefill with a block table,
    single-token decode steps, free. Exactly what an external serving layer
    does, so every metric family the dashboard needs lights up."""
    from nxdi_tpu.runtime.block_manager import BlockSpaceManager

    tc = app.tpu_config
    tel = app.telemetry
    mgr = BlockSpaceManager(tc.pa_num_blocks, tc.pa_block_size, telemetry=tel)
    width = -(-tc.seq_len // tc.pa_block_size)
    rng = np.random.default_rng(1)

    for rid in range(n_requests):
        prompt = rng.integers(4, 200, size=(7 + rid,)).astype(np.int32)
        span = tel.start_request(tokens_in=len(prompt))
        span.phase("pad")
        mgr.ensure_capacity(rid, len(prompt) + max_new_tokens)
        bt = mgr.block_table(rid, width)[None, :]
        span.phase("prefill")
        pos = np.arange(len(prompt), dtype=np.int32)[None, :]
        out = app.forward(
            prompt[None, :], pos,
            last_token_index=np.array([len(prompt) - 1], np.int32),
            block_table=bt,
        )
        tok = int(np.asarray(out["tokens"])[0, 0])
        span.first_token()
        span.tokens(1)
        span.phase("decode")
        cur = len(prompt)
        for _ in range(max_new_tokens - 1):
            t0 = tel.clock()
            out = app.forward(
                np.array([[tok]], np.int32), np.array([[cur]], np.int32),
                last_token_index=np.zeros((1,), np.int32),
                block_table=bt,
            )
            tok = int(np.asarray(out["tokens"])[0, 0])
            span.tokens(1, tel.clock() - t0)
            cur += 1
        span.finish()
        mgr.free_seq(rid)


def run_contiguous_demo(app, n_requests: int, max_new_tokens: int) -> None:
    from nxdi_tpu.generation.hf_adapter import HuggingFaceGenerationAdapter

    adapter = HuggingFaceGenerationAdapter(app)
    rng = np.random.default_rng(1)
    for rid in range(n_requests):
        prompt = rng.integers(4, 200, size=(1, 7 + rid)).astype(np.int64)
        adapter.generate(prompt, max_new_tokens=max_new_tokens)


def main(argv: Optional[List[str]] = None) -> int:
    parser = argparse.ArgumentParser(
        prog="python -m nxdi_tpu.cli.metrics",
        description="serving-telemetry snapshot/export of the tiny reference app",
    )
    setup_metrics_parser(parser)
    args = parser.parse_args(argv)

    if args.on_cpu:
        use_cpu_backend()

    tpu_kwargs = dict(
        tp_degree=1,
        batch_size=1,
        dtype="bfloat16",
        skip_warmup=True,
        telemetry=args.detail,
    )
    if not args.contiguous:
        tpu_kwargs.update(
            is_block_kv_layout=True, pa_block_size=8, pa_num_blocks=32
        )
    from nxdi_tpu.config import OnDeviceSamplingConfig

    tpu_kwargs["on_device_sampling_config"] = OnDeviceSamplingConfig()

    t0 = time.time()
    _note(args.quiet, "[metrics] building + loading the reference app ...")
    app = build_loaded_reference_app(tpu_kwargs)
    _note(args.quiet, f"[metrics] loaded in {time.time() - t0:.1f}s; "
                      f"running {args.requests} demo requests")
    if args.contiguous:
        run_contiguous_demo(app, args.requests, args.max_new_tokens)
    else:
        run_paged_demo(app, args.requests, args.max_new_tokens)

    tel = app.telemetry
    if not args.quiet:
        # the registry's interpolated percentile estimator, one line per
        # latency family (same numbers the JSON snapshot rows carry).
        # Percentiles come from the ONE series_snapshot copy so n and
        # p50/p95/p99 can never describe different populations mid-traffic
        from nxdi_tpu.telemetry import percentile_from_buckets

        for fam in ("nxdi_dispatch_seconds", "nxdi_request_ttft_seconds",
                    "nxdi_request_tpot_seconds"):
            hist = tel.registry.get(fam)
            if hist is None:
                continue
            for key, (counts, _, count) in sorted(hist.series_snapshot().items()):
                if not count:
                    continue
                tag = ",".join(
                    f"{k}={v}" for k, v in hist.labels_of(key).items()
                )
                pcts = " ".join(
                    "p%d=%.2fms" % (
                        p,
                        percentile_from_buckets(hist.bounds, counts, count, p)
                        * 1e3,
                    )
                    for p in (50, 95, 99)
                )
                _note(False, f"[metrics] {fam}{{{tag}}} n={count} {pcts}")
    if args.format in ("prom", "both"):
        print(tel.prometheus_text(), end="")
    if args.format in ("json", "both"):
        print(json.dumps(tel.snapshot(), indent=2))
    if args.json_path:
        with open(args.json_path, "w") as f:
            json.dump(tel.snapshot(), f, indent=2)
    if args.perfetto_path:
        tel.write_perfetto_trace(args.perfetto_path)
        _note(args.quiet, f"[metrics] Perfetto trace: {args.perfetto_path} "
                          "(open in ui.perfetto.dev)")

    if args.serve:
        server = tel.serve(host=args.host, port=args.port)
        _note(args.quiet,
              f"[metrics] serving http://{args.host}:{server.port}/metrics "
              "(/metrics.json, /trace.json) — Ctrl-C to stop")
        try:
            while True:
                time.sleep(3600)
        except KeyboardInterrupt:
            server.shutdown()
    return 0


if __name__ == "__main__":
    sys.exit(main())
