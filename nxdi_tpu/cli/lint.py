"""``python -m nxdi_tpu.cli.lint`` — the static program auditor as a CLI.

Audits every AOT-lowered submodel program of an application (donation,
collective budget vs the sharding policy, dtype drift, baked constants,
required kernel strategies) and emits a per-model JSON report. Exit status:
0 = clean, 1 = violations at/above ``--fail-on``, 2 = usage error.

Weights are never loaded: the auditor traces/lowers from abstract shape
structs exactly like ``aot_compile``, so a TPU-shaped config can be linted
from any box whose compiler can lower it.

Usage:

  # the llama CPU-mesh reference app (tiny random-config llama; the same
  # program set tier-1 audits), e.g. at tp=8 over virtual CPU devices:
  python -m nxdi_tpu.cli.lint --reference-app --tp-degree 8 --json report.json

  # a real checkpoint:
  python -m nxdi_tpu.cli.lint --model-type llama --model-path /ckpt \\
      --tp-degree 8 --seq-len 1024 --on-device-sampling

  # the host-plane concurrency auditor (source-level; no model needed):
  python -m nxdi_tpu.cli.lint --concurrency

  # both, one merged report:
  python -m nxdi_tpu.cli.lint --reference-app --all --json report.json
"""

from __future__ import annotations

import argparse
import json
import sys
from typing import List, Optional

from nxdi_tpu.cli import use_cpu_backend


def setup_lint_parser(p: argparse.ArgumentParser) -> None:
    p.add_argument("--model-type", default=None, help="registry key, e.g. llama")
    p.add_argument("--model-path", default=None, help="HF checkpoint directory")
    p.add_argument("--reference-app", action="store_true",
                   help="audit the tiny random llama CPU-mesh reference app "
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
                   help="JSON dict of extra TpuConfig kwargs (inline or @file) "
                        "merged over the flags above — the escape hatch for "
                        "every knob this parser does not spell out")
    p.add_argument("--submodels", default=None,
                   help="comma-separated submodel tags to audit (default: all)")
    p.add_argument("--checkers", default=None,
                   help="comma-separated checker names (default: all; see "
                        "nxdi_tpu.analysis.CHECKERS)")
    p.add_argument("--const-threshold", type=int, default=None,
                   help="baked-constant size threshold in bytes")
    p.add_argument("--fail-on", choices=["error", "warning"], default="error")
    p.add_argument("--concurrency", action="store_true",
                   help="run the host-plane concurrency auditor (lock "
                        "discipline, lock ordering, thread hygiene) over the "
                        "nxdi_tpu sources instead of the program audit; "
                        "needs no model or checkpoint")
    p.add_argument("--all", dest="run_all", action="store_true",
                   help="run the program audit AND the concurrency auditor, "
                        "merged into one JSON report")
    p.add_argument("--json", dest="json_path", default=None,
                   help="write the JSON report here ('-' = stdout, default)")
    p.add_argument("-q", "--quiet", action="store_true",
                   help="suppress the human-readable findings summary")


def _load_json_arg(arg):
    if arg.startswith("@"):
        with open(arg[1:]) as f:
            return json.load(f)
    return json.loads(arg)


def _tpu_config_kwargs(args) -> dict:
    from nxdi_tpu.config import OnDeviceSamplingConfig

    kw = dict(
        tp_degree=args.tp_degree,
        batch_size=args.batch_size,
        dtype=args.dtype,
        skip_warmup=True,
        decode_steps_per_dispatch=args.decode_steps_per_dispatch,
        sequence_parallel_enabled=args.sequence_parallel_enabled,
    )
    if args.seq_len is not None:
        kw["seq_len"] = args.seq_len
        kw["max_context_length"] = args.max_context_length or args.seq_len // 2
    elif args.max_context_length is not None:
        kw["max_context_length"] = args.max_context_length
    on_device = args.on_device_sampling
    if on_device is None and args.reference_app:
        on_device = True  # the reference app serves with on-device sampling
    if on_device:
        kw["on_device_sampling_config"] = OnDeviceSamplingConfig()
    if args.tpu_config_json:
        kw.update(_load_json_arg(args.tpu_config_json))
    return kw


def build_reference_app(tpu_kwargs: dict):
    """The llama CPU-mesh reference app: the tiny random llama config the
    tier-1 suite compiles everywhere — 2 scanned decoder layers, GQA heads,
    vocab 256 — on the CPU backend's virtual-device mesh."""
    from nxdi_tpu.config import TpuConfig
    from nxdi_tpu.models.llama import modeling_llama as ml
    from nxdi_tpu.runtime.application import TpuModelForCausalLM

    kw = dict(seq_len=64, max_context_length=32)
    kw.update(tpu_kwargs)
    tcfg = TpuConfig(**kw)
    cfg = ml.LlamaInferenceConfig(
        tcfg,
        hidden_size=64,
        intermediate_size=128,
        num_hidden_layers=2,
        num_attention_heads=4,
        num_key_value_heads=2,
        head_dim=16,
        vocab_size=256,
        rms_norm_eps=1e-5,
        rope_theta=10000.0,
    )
    return TpuModelForCausalLM("<reference-app>", cfg, model_family=ml)


def build_checkpoint_app(args, tpu_kwargs: dict):
    from nxdi_tpu.config import TpuConfig
    from nxdi_tpu.generation.hf_adapter import load_pretrained_config
    from nxdi_tpu.models.registry import get_family
    from nxdi_tpu.runtime.application import TpuModelForCausalLM

    family, cfg_cls = get_family(args.model_type)
    tcfg = TpuConfig(**tpu_kwargs)
    config = cfg_cls(tcfg, load_config=load_pretrained_config(args.model_path))
    return TpuModelForCausalLM(args.model_path, config, model_family=family)


def run_concurrency_audit():
    """The host-plane concurrency auditor over the installed nxdi_tpu tree
    (source-level, jax-free — lintable from any box)."""
    import os

    from nxdi_tpu.analysis.concurrency import analyze_paths

    pkg_dir = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    return analyze_paths([pkg_dir], repo_root=os.path.dirname(pkg_dir))


def _emit(payload: str, json_path: Optional[str]) -> None:
    if json_path and json_path != "-":
        with open(json_path, "w") as f:
            f.write(payload + "\n")
    else:
        print(payload)


def main(argv: Optional[List[str]] = None) -> int:
    parser = argparse.ArgumentParser(
        prog="python -m nxdi_tpu.cli.lint",
        description="static lint over every AOT-lowered submodel program",
    )
    setup_lint_parser(parser)
    args = parser.parse_args(argv)

    conc = None
    if args.concurrency or args.run_all:
        conc = run_concurrency_audit()

    if args.concurrency and not args.run_all:
        # source-level only: no app to build, no compiler to invoke
        _emit(json.dumps(conc.to_dict(), indent=2, sort_keys=True),
              args.json_path)
        if not args.quiet:
            for f in conc.findings:
                print(str(f), file=sys.stderr)
            print(
                f"lint: concurrency audit — {len(conc.findings)} findings, "
                f"{len(conc.lock_order_cycles)} lock-order cycles, "
                f"{len(conc.lock_owners)} lock-owning classes",
                file=sys.stderr,
            )
        return 0 if conc.ok else 1

    if not args.reference_app and not (args.model_type and args.model_path):
        parser.print_usage(sys.stderr)
        print("lint: provide --reference-app or --model-type + --model-path",
              file=sys.stderr)
        return 2

    if args.reference_app or args.on_cpu:
        use_cpu_backend(max(8, args.tp_degree))

    from nxdi_tpu.analysis import CHECKERS, audit_application
    from nxdi_tpu.analysis.auditor import CROSS_PROGRAM

    checkers = None
    if args.checkers:
        checkers = [c.strip() for c in args.checkers.split(",") if c.strip()]
        # the cross-program passes (cache_format, param_format) are not per-program
        unknown = sorted(set(checkers) - set(CHECKERS) - set(CROSS_PROGRAM))
        if unknown:
            print(f"lint: unknown checkers {unknown}; have {sorted(CHECKERS)}",
                  file=sys.stderr)
            return 2
    submodels = None
    if args.submodels:
        submodels = [s.strip() for s in args.submodels.split(",") if s.strip()]

    tpu_kwargs = _tpu_config_kwargs(args)
    app = (
        build_reference_app(tpu_kwargs)
        if args.reference_app
        else build_checkpoint_app(args, tpu_kwargs)
    )

    audit_kwargs = dict(submodels=submodels, checkers=checkers)
    if args.const_threshold is not None:
        audit_kwargs["const_threshold"] = args.const_threshold
    report = audit_application(app, **audit_kwargs)

    if conc is not None:
        # --all: one merged report — the program audit's payload plus a
        # `concurrency` section, failing if either side fails
        merged = json.loads(report.to_json(fail_on=args.fail_on))
        merged["concurrency"] = conc.to_dict()
        payload = json.dumps(merged, indent=2, sort_keys=True)
    else:
        payload = report.to_json(fail_on=args.fail_on)
    _emit(payload, args.json_path)

    if not args.quiet:
        for f in report.findings:
            print(str(f), file=sys.stderr)
        n_err = len(report.errors())
        n_warn = len(report.findings) - n_err
        print(
            f"lint: {len(report.programs)} programs audited, "
            f"{n_err} errors, {n_warn} warnings",
            file=sys.stderr,
        )
        if conc is not None:
            for f in conc.findings:
                print(str(f), file=sys.stderr)
            print(
                f"lint: concurrency audit — {len(conc.findings)} findings, "
                f"{len(conc.lock_order_cycles)} lock-order cycles",
                file=sys.stderr,
            )
    ok = report.ok(fail_on=args.fail_on) and (conc is None or conc.ok)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
