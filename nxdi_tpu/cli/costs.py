"""``python -m nxdi_tpu.cli.costs`` — the per-program cost observatory CLI.

Prints one CostSheet row per AOT-lowered ``(submodel, bucket[, steps])``
program: FLOPs and HBM bytes per dispatch (XLA's ``cost_analysis``/
``memory_analysis`` cross-checked against the analytic model —
``source=analytic`` marks backends that could not answer), the roofline
classification against the declared chip spec, the theoretical minimum
dispatch latency, and the per-chip HBM-fit account (weights + max-live KV +
temp vs capacity).

Weights never load — programs are lowered/compiled from abstract shape
structs exactly like ``aot_compile``, so TPU-shaped configs cost out from
any box whose compiler can lower them.

Exit status (the gate, like ``cli.lint``): 0 = every program fits per-chip
HBM, 1 = at least one is over budget, 2 = usage error.

Usage:

  # the llama CPU-mesh reference app (the tier-1 program set):
  python -m nxdi_tpu.cli.costs --reference-app

  # a real checkpoint at serving shape, costed for a v5p part:
  python -m nxdi_tpu.cli.costs --model-type llama --model-path /ckpt \\
      --tp-degree 8 --seq-len 8192 --on-device-sampling --chip v5p

  # what-if on a custom part (fields override v5e):
  python -m nxdi_tpu.cli.costs --reference-app \\
      --chip '{"hbm_gib": 8, "hbm_gbs": 400}'
"""

from __future__ import annotations

import argparse
import json
import sys
from typing import List, Optional

from nxdi_tpu.cli import use_cpu_backend


def setup_costs_parser(p: argparse.ArgumentParser) -> None:
    p.add_argument("--model-type", default=None, help="registry key, e.g. llama")
    p.add_argument("--model-path", default=None, help="HF checkpoint directory")
    p.add_argument("--reference-app", action="store_true",
                   help="cost the tiny random llama CPU-mesh reference app "
                        "(no checkpoint needed; forces the CPU backend)")
    p.add_argument("--on-cpu", action="store_true",
                   help="run the compiler on the CPU backend (virtual devices "
                        "sized to the parallel degrees)")
    p.add_argument("--tp-degree", type=int, default=1)
    p.add_argument("--seq-len", type=int, default=None)
    p.add_argument("--max-context-length", type=int, default=None)
    p.add_argument("--batch-size", type=int, default=1)
    p.add_argument("--dtype", "--torch-dtype", dest="dtype", default="bfloat16")
    p.add_argument("--on-device-sampling", action="store_true", default=None)
    p.add_argument("--decode-steps-per-dispatch", type=int, default=1)
    p.add_argument("--sequence-parallel-enabled", action="store_true")
    p.add_argument("--tpu-config-json", default=None,
                   help="JSON dict of extra TpuConfig kwargs (inline or @file)")
    p.add_argument("--chip", default=None,
                   help="chip spec: a name (v4|v5e|v5p|v6e) or an inline JSON "
                        "dict of ChipSpec overrides; default = the config's "
                        "chip, else v5e")
    p.add_argument("--format", choices=["text", "json", "both"], default="text",
                   help="stdout format (default: text table)")
    p.add_argument("--json", dest="json_path", default=None,
                   help="also write the JSON sheet table to this file")
    p.add_argument("-q", "--quiet", action="store_true",
                   help="suppress the stderr summary line")


def _parse_chip_arg(arg: Optional[str]):
    if arg is None:
        return None
    arg = arg.strip()
    if arg.startswith("{"):
        return json.loads(arg)
    return arg


def format_table(sheets) -> str:
    """The human table: one row per program, aligned columns."""
    header = (
        "program", "src", "GFLOP", "HBM MB", "bound", "floor ms", "fit"
    )
    rows = [header]
    for s in sheets:
        f = s.fit
        pct = 100.0 * f["resident_bytes"] / max(f["hbm_capacity_bytes"], 1.0)
        rows.append((
            s.label,
            s.source,
            f"{s.flops / 1e9:.3f}",
            f"{s.hbm_bytes / 1e6:.3f}",
            s.bound,
            f"{s.floor_s * 1e3:.4f}",
            ("ok" if f["fits"] else "OVER") + f" ({pct:.1f}%)",
        ))
    widths = [max(len(r[i]) for r in rows) for i in range(len(header))]
    lines = []
    for i, r in enumerate(rows):
        lines.append("  ".join(c.ljust(w) for c, w in zip(r, widths)).rstrip())
        if i == 0:
            lines.append("  ".join("-" * w for w in widths))
    return "\n".join(lines)


def main(argv: Optional[List[str]] = None) -> int:
    parser = argparse.ArgumentParser(
        prog="python -m nxdi_tpu.cli.costs",
        description="per-program FLOP/HBM cost sheets + roofline + HBM fit",
    )
    setup_costs_parser(parser)
    args = parser.parse_args(argv)

    if not args.reference_app and not (args.model_type and args.model_path):
        parser.print_usage(sys.stderr)
        print("costs: provide --reference-app or --model-type + --model-path",
              file=sys.stderr)
        return 2

    if args.reference_app or args.on_cpu:
        use_cpu_backend(max(8, args.tp_degree))

    from nxdi_tpu.analysis.costs import cost_sheets, resolve_chip
    from nxdi_tpu.cli.lint import (
        _tpu_config_kwargs,
        build_checkpoint_app,
        build_reference_app,
    )

    # validate --chip BEFORE the (expensive) app build/compile: a typo'd
    # name or bad JSON is a usage error, not a traceback after 30s of work
    try:
        chip_arg = _parse_chip_arg(args.chip)
        resolve_chip(None, override=chip_arg)
    except (json.JSONDecodeError, TypeError, ValueError) as e:
        print(f"costs: bad --chip: {e}", file=sys.stderr)
        return 2

    tpu_kwargs = _tpu_config_kwargs(args)
    app = (
        build_reference_app(tpu_kwargs)
        if args.reference_app
        else build_checkpoint_app(args, tpu_kwargs)
    )
    sheets = cost_sheets(app, chip=chip_arg, compile_missing=True)
    chip = resolve_chip(app.tpu_config, override=chip_arg)

    payload = {
        "chip": chip.to_dict(),
        "programs": [s.to_dict() for s in sheets],
        "ok": all(s.fit["fits"] for s in sheets),
    }
    if args.format in ("text", "both"):
        print(format_table(sheets))
    if args.format in ("json", "both"):
        print(json.dumps(payload, indent=2))
    if args.json_path:
        with open(args.json_path, "w") as f:
            json.dump(payload, f, indent=2)

    over = [s for s in sheets if not s.fit["fits"]]
    mismatched = [s for s in sheets if s.mismatch]
    if not args.quiet:
        fit0 = sheets[0].fit if sheets else {}
        print(
            f"costs: {len(sheets)} programs on {chip.name} "
            f"({chip.bf16_tflops:g} bf16 TFLOP/s, {chip.hbm_gbs:g} GB/s, "
            f"{chip.hbm_gib:g} GiB); weights "
            f"{fit0.get('weight_bytes_per_chip', 0) / 2**30:.3f} GiB/chip + "
            f"max-live KV {fit0.get('kv_bytes_per_chip', 0) / 2**30:.3f} "
            f"GiB/chip; {len(over)} over budget, "
            f"{len(mismatched)} cost-model mismatches",
            file=sys.stderr,
        )
        for s in mismatched:
            print(f"costs: WARNING {s.mismatch}", file=sys.stderr)
    return 0 if not over else 1


if __name__ == "__main__":
    sys.exit(main())
