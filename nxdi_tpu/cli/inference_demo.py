"""inference_demo CLI — compile/load a model, check accuracy, generate, benchmark.

The user-facing entry point mirroring the reference's ``inference_demo``
(inference_demo.py:97 setup_run_parser, :438 create_neuron_config,
:495 run_inference, :784 main): same flag vocabulary where concepts transfer,
so reference users can bring their command lines across.

Usage:
  python -m nxdi_tpu.cli.inference_demo run --model-type llama \
      --model-path /path/to/hf_ckpt --compiled-model-path /tmp/compiled \
      --tp-degree 8 --batch-size 1 --seq-len 1024 --on-device-sampling \
      --prompt "I believe the meaning of life is" \
      --check-accuracy-mode token-matching --benchmark
"""

from __future__ import annotations

import argparse
import json
import logging
import sys
from typing import List, Optional

import numpy as np

logger = logging.getLogger("nxdi_tpu")

CHECK_ACCURACY_MODES = ("skip", "token-matching", "logit-matching")


def setup_run_parser(parser: argparse.ArgumentParser) -> None:
    """Flag surface (reference: inference_demo.py:97-410, subset growing per round)."""
    p = parser
    p.add_argument("--model-type", required=True, help="registry key, e.g. llama, qwen2")
    p.add_argument("--task-type", default="causal-lm", choices=["causal-lm"])
    p.add_argument("--model-path", required=True)
    p.add_argument("--compiled-model-path", default=None)
    p.add_argument("--skip-compile", action="store_true")
    p.add_argument("--skip-warmup", action="store_true")
    p.add_argument("--on-cpu", action="store_true", help="run on the CPU backend")

    # shapes / dtypes (--max-length/--n-positions and --max-batch-size/
    # --max-num-seqs are the reference's spellings for the same knobs)
    p.add_argument("--batch-size", "--max-batch-size", "--max-num-seqs",
                   dest="batch_size", type=int, default=1)
    p.add_argument("--ctx-batch-size", type=int, default=None)
    p.add_argument("--tkg-batch-size", type=int, default=None)
    p.add_argument("--seq-len", "--max-length", "--n-positions",
                   dest="seq_len", type=int, default=1024)
    p.add_argument("--max-context-length", type=int, default=None)
    p.add_argument("--max-new-tokens", type=int, default=64)
    p.add_argument("--torch-dtype", "--dtype", dest="dtype", default="bfloat16")
    p.add_argument("--attention-dtype", default=None,
                   help="override the attention compute dtype (e.g. float32 "
                        "attention under a bfloat16 model)")
    p.add_argument("--rpl-reduce-dtype", default=None,
                   help="row-parallel reduction dtype (psum accumulation)")
    p.add_argument("--padding-side", default="right", choices=["right", "left"])
    p.add_argument("--allow-input-truncation", action="store_true",
                   help="truncate prompts longer than --max-context-length "
                        "to their FIRST max-context-length tokens instead of "
                        "raising (head-keep, matching the reference's "
                        "negative pad in model_wrapper.py:766)")

    # parallelism
    p.add_argument("--tp-degree", type=int, default=1)
    p.add_argument("--cp-degree", type=int, default=1)
    p.add_argument("--ep-degree", type=int, default=1)
    p.add_argument("--attention-dp-degree", type=int, default=1)
    p.add_argument("--pp-degree", type=int, default=1)
    p.add_argument("--pp-microbatches", type=int, default=0,
                   help="GPipe microbatches per pipelined forward (0 = pp-degree)")
    p.add_argument("--moe-ep-degree", type=int, default=None,
                   help="hybrid MoE expert-parallel degree (experts over ep, "
                        "expert intermediates over tp)")
    p.add_argument("--moe-cte-ep-degree", type=int, default=None,
                   help="PER-PHASE hybrid MoE: prefill expert-parallel degree "
                        "(reference: HybridShardingConfig moe_cte_ep_degree)")
    p.add_argument("--moe-tkg-ep-degree", type=int, default=None,
                   help="PER-PHASE hybrid MoE: decode expert-parallel degree "
                        "(a multiple of --moe-cte-ep-degree; expert weights "
                        "are duplicated per regime)")
    p.add_argument("--moe-tp-degree", type=int, default=None,
                   help="expert-intermediate TP degree inside a hybrid TPxEP "
                        "MoE layout (reference: moe_tp_degree)")
    p.add_argument("--mlp-cp-degree", type=int, default=1,
                   help="MLP context-parallel degree (prefill MLP sharded "
                        "over the sequence; subsumed by SP when equal). "
                        "Must equal --tp-degree or 1 — TIGHTER than the "
                        "reference's divides-tp rule: GSPMD shards S over "
                        "the whole model-parallel axis, so intermediate "
                        "degrees (e.g. tp=8 mlp-cp=2) have no mesh sub-axis "
                        "to land on and are rejected loudly")
    p.add_argument("--sequence-parallel-enabled", action="store_true")
    p.add_argument("--flash-decoding-enabled", action="store_true")
    p.add_argument("--vocab-parallel", type=int, choices=[0, 1], default=None,
                   help="shard embedding/lm_head over the vocab dim (default "
                        "on when divisible)")
    p.add_argument("--logical-nc-config", type=int, default=1,
                   help="cores ganged per logical device (v5p megacore analog "
                        "of the reference's LNC)")
    p.add_argument("--xla-flags", default=None,
                   help="extra XLA_FLAGS appended before backend init — the "
                        "TPU-native surface for collective/compiler tuning "
                        "(the reference's cc-pipeline-tiling / DGE knobs)")

    # sampling
    p.add_argument("--on-device-sampling", action="store_true")
    p.add_argument("--do-sample", action="store_true")
    p.add_argument("--top-k", type=int, default=1)
    p.add_argument("--top-p", type=float, default=1.0)
    p.add_argument("--temperature", type=float, default=1.0)
    p.add_argument("--global-topk", type=int, default=256)
    p.add_argument("--sampling-dp-degree", type=int, default=1,
                   help=">1 shards the on-device sampler's top-k stages over "
                        "the batch (reference: DataParallelSampler)")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--output-logits", action="store_true",
                   help="emit full-vocab logits as an extra model output")

    # bucketing
    p.add_argument("--enable-bucketing", action="store_true")
    p.add_argument("--context-encoding-buckets", nargs="+", type=int, default=None)
    p.add_argument("--token-generation-buckets", nargs="+", type=int, default=None)
    p.add_argument("--prefix-buckets", nargs="+", type=int, default=None,
                   help="prefix lengths for the 2-D prefix-prefill bucket "
                        "grid (prefix caching / chunked prefill)")
    p.add_argument("--long-context-mode", type=int, choices=[0, 1], default=None,
                   help="coarsen bucket ladders for 32k+ contexts (auto-on at "
                        ">=32k; pass 0/1 to force; reference: "
                        "enable_long_context_mode config.py:578)")
    p.add_argument("--dynamic-tree-steps", type=int, default=None,
                   help="dynamic token tree depth (reference: "
                        "dynamic_token_tree.py step)")
    p.add_argument("--dynamic-tree-branching", type=int, default=2,
                   help="children per expanded node")
    p.add_argument("--dynamic-tree-num-inputs", type=int, default=1,
                   help="nodes expanded per step (by cumulative probability)")

    # execution
    p.add_argument("--async-mode", action="store_true")
    p.add_argument("--is-continuous-batching", action="store_true")

    # KV layouts
    p.add_argument("--is-block-kv-layout", action="store_true",
                   help="paged (vLLM-style) KV cache")
    p.add_argument("--pa-block-size", type=int, default=128)
    p.add_argument("--pa-num-blocks", type=int, default=None)
    p.add_argument("--window-sized-kv", action="store_true",
                   help="ring KV cache sized to --sliding-window slots")
    p.add_argument("--sliding-window", type=int, default=None)
    p.add_argument("--kv-cache-batch-size", type=int, default=None,
                   help="KV cache rows when they exceed the run batch "
                        "(continuous batching over more sequences than a "
                        "single dispatch carries)")
    p.add_argument("--windowed-context-encoding-size", type=int, default=None,
                   help="windowed CTE chunk width (reference: WCTE)")

    # Pallas kernels
    p.add_argument("--attn-kernel-enabled", action="store_true",
                   help="flash prefill kernel")
    p.add_argument("--attn-tkg-kernel-enabled", action="store_true",
                   help="flash decode kernel")
    p.add_argument("--attn-block-tkg-kernel-enabled", action="store_true",
                   help="paged decode kernel (reads through the block table)")
    p.add_argument("--fused-qkv", action="store_true",
                   help="pack q/k/v into one interleaved projection weight")
    p.add_argument("--qkv-kernel-enabled", action="store_true",
                   help="Pallas fused-QKV matmul kernel (requires --fused-qkv)")
    p.add_argument("--mlp-kernel-enabled", action="store_true",
                   help="Pallas fused gate/up/down MLP kernel")

    # speculation
    p.add_argument("--draft-model-path", default=None)
    p.add_argument("--draft-model-type", default=None, help="defaults to --model-type")
    p.add_argument("--draft-model-tp-degree", type=int, default=None,
                   help="run the draft at its own (smaller) tp degree "
                        "(unfused speculation only)")
    p.add_argument("--speculation-length", "--medusa-speculation-length",
                   dest="speculation_length", type=int, default=0)
    p.add_argument("--enable-fused-speculation", action="store_true")
    p.add_argument("--enable-eagle-speculation", action="store_true")
    p.add_argument("--is-eagle3", action="store_true")
    p.add_argument("--is-medusa", action="store_true")
    p.add_argument("--num-medusa-heads", type=int, default=0)
    p.add_argument(
        "--medusa-tree", "--medusa-tree-json", dest="medusa_tree", default=None,
        help="token tree: path to a JSON file of paths, or inline JSON "
             "(reference: examples/medusa_mc_sim_7b_63.json)",
    )
    p.add_argument(
        "--token-tree-config", "--token-tree-json", dest="token_tree_config",
        default=None,
        help="EAGLE token tree: path to a JSON file of paths, or inline JSON",
    )

    # LoRA serving
    p.add_argument("--enable-lora", action="store_true")
    p.add_argument("--max-loras", type=int, default=1)
    p.add_argument("--max-lora-rank", type=int, default=16)
    p.add_argument(
        "--lora-ckpt-path",
        action="append",
        default=None,
        help="adapter_name=/path/to/peft_adapter (repeatable)",
    )
    p.add_argument("--lora-ckpt-json", default=None,
                   help='JSON {"adapter_name": "/path"} — file path or inline')
    p.add_argument("--target-modules", nargs="+", default=None,
                   help="projection names LoRA attaches to (default q/k/v/o)")
    p.add_argument("--adapter-id", action="append", default=None,
                   help="per-prompt adapter name (repeatable, aligns with --prompt)")

    # quantization
    p.add_argument("--quantized", action="store_true")
    p.add_argument("--quantization-dtype", default="int8")
    p.add_argument("--quantization-type", default="per_tensor_symmetric",
                   help="per_tensor_symmetric | per_channel_symmetric")
    p.add_argument("--quantized-checkpoints-path", default=None,
                   help="pre-quantized artifact dir (written by "
                        "save_quantized_state_dict); skips on-the-fly "
                        "quantization at load")
    p.add_argument("--activation-quantization-type", default=None,
                   choices=["dynamic", "static"],
                   help="int8 activation quantization: per-token scales on "
                        "the hot path (dynamic) or calibrated per-tensor "
                        "scales from the quantized checkpoint (static)")
    p.add_argument("--quantize-clamp-bound", type=float, default=None,
                   help="clamp |activations| before quantizing")
    p.add_argument("--kv-cache-quant", action="store_true")
    p.add_argument("--kv-scale-mode", default="direct_cast",
                   choices=["direct_cast", "per_tensor", "per_key", "per_channel"],
                   help="fp8/int8 KV store: raw cast, scalar scales, or "
                        "per-layer per-key/per-channel scale buffers "
                        "(--kv-scales-path)")
    p.add_argument("--k-scale", type=float, default=1.0)
    p.add_argument("--v-scale", type=float, default=1.0)
    p.add_argument("--kv-quant-dtype", default="float8_e4m3",
                   help="KV store dtype (float8_e4m3 | float8_e5m2 | int8)")
    p.add_argument("--kv-scales-path", default=None,
                   help=".npz from kvcache.calibration.calibrate_kv_scales "
                        "(required for per_key/per_channel)")

    # accuracy / benchmark
    p.add_argument("--check-accuracy-mode", default="skip", choices=CHECK_ACCURACY_MODES)
    p.add_argument("--divergence-difference-tol", type=float, default=0.001)
    p.add_argument("--tol-map", default=None,
                   help='JSON {"position": tol} of per-index tolerance '
                        "relaxations for logit matching — file path or inline")
    p.add_argument("--num-tokens-to-check", type=int, default=None,
                   help="logit-match only the first N generated positions")
    p.add_argument("--expected-outputs-path", default=None,
                   help="token-matching golden from a saved .json/.npz of "
                        "token ids instead of running the HF model")
    p.add_argument("--input-capture-save-dir", default=None,
                   help="snapshot every dispatched (padded) input batch to "
                        "this directory (reference: input capture)")
    p.add_argument(
        "--capture-output-dir", default=None,
        help="on logit-matching failure, write a divergence repro bundle here "
             "(reference: --capture-indices auto)",
    )
    p.add_argument("--benchmark", action="store_true")
    p.add_argument("--num-runs", type=int, default=5)

    # inputs
    p.add_argument("--prompt", action="append", default=None)
    p.add_argument("--input-ids", default=None, help="JSON list-of-lists of token ids")
    p.add_argument("--pad-token-id", type=int, default=0)
    p.add_argument("--verbose", action="store_true")


def create_tpu_config(args):
    """argparse namespace -> TpuConfig (reference: create_neuron_config
    inference_demo.py:438)."""
    from nxdi_tpu.config import LoraServingConfig, OnDeviceSamplingConfig, TpuConfig

    lora_cfg = None
    if args.enable_lora:
        paths = dict(e.split("=", 1) for e in (args.lora_ckpt_path or []))
        if args.lora_ckpt_json:
            paths.update(_load_json_arg(args.lora_ckpt_json))
        lora_kwargs = {}
        if args.target_modules:
            lora_kwargs["target_modules"] = list(args.target_modules)
        lora_cfg = LoraServingConfig(
            max_loras=max(args.max_loras, len(paths)),
            max_lora_rank=args.max_lora_rank,
            lora_ckpt_paths=paths or None,
            **lora_kwargs,
        )

    odsc = None
    if args.on_device_sampling:
        odsc = OnDeviceSamplingConfig(
            do_sample=args.do_sample,
            top_k=args.top_k,
            top_p=args.top_p,
            temperature=args.temperature,
            global_topk=args.global_topk,
            dp_sampling=args.sampling_dp_degree > 1,
        )
    return TpuConfig(
        batch_size=args.batch_size,
        ctx_batch_size=args.ctx_batch_size or args.batch_size,
        tkg_batch_size=args.tkg_batch_size or args.batch_size,
        seq_len=args.seq_len,
        max_context_length=args.max_context_length or args.seq_len // 2,
        padding_side=args.padding_side,
        dtype="float32" if args.on_cpu else args.dtype,
        on_cpu=args.on_cpu,
        tp_degree=args.tp_degree,
        cp_degree=args.cp_degree,
        ep_degree=args.ep_degree,
        attention_dp_degree=args.attention_dp_degree,
        pp_degree=args.pp_degree,
        pp_microbatches=args.pp_microbatches,
        moe_ep_degree=args.moe_ep_degree,
        # a one-sided flag defaults the other side to a valid regime: the
        # unset cte degree stays 1 (TP-heavy prefill), the unset tkg degree
        # matches cte (tkg must be a multiple of cte)
        hybrid_sharding_config=(
            {"moe_cte_ep_degree": args.moe_cte_ep_degree or 1,
             "moe_tkg_ep_degree": args.moe_tkg_ep_degree
             or args.moe_cte_ep_degree or 1}
            if args.moe_cte_ep_degree or args.moe_tkg_ep_degree
            else None
        ),
        moe_tp_degree=args.moe_tp_degree,
        mlp_cp_degree=args.mlp_cp_degree,
        sequence_parallel_enabled=args.sequence_parallel_enabled,
        flash_decoding_enabled=args.flash_decoding_enabled,
        logical_nc_config=args.logical_nc_config,
        output_logits=args.output_logits,
        attention_dtype=args.attention_dtype,
        rpl_reduce_dtype=args.rpl_reduce_dtype,
        prefix_buckets=args.prefix_buckets,
        windowed_context_encoding_size=args.windowed_context_encoding_size,
        **({"kv_cache_batch_size": args.kv_cache_batch_size}
           if args.kv_cache_batch_size is not None else {}),
        is_continuous_batching=args.is_continuous_batching,
        is_block_kv_layout=args.is_block_kv_layout,
        pa_block_size=args.pa_block_size,
        pa_num_blocks=args.pa_num_blocks,
        window_sized_kv=args.window_sized_kv,
        sliding_window=args.sliding_window,
        attn_kernel_enabled=args.attn_kernel_enabled,
        attn_tkg_kernel_enabled=args.attn_tkg_kernel_enabled,
        attn_block_tkg_kernel_enabled=args.attn_block_tkg_kernel_enabled,
        fused_qkv=args.fused_qkv,
        qkv_kernel_enabled=args.qkv_kernel_enabled,
        mlp_kernel_enabled=args.mlp_kernel_enabled,
        on_device_sampling_config=odsc,
        enable_bucketing=args.enable_bucketing,
        context_encoding_buckets=args.context_encoding_buckets,
        token_generation_buckets=args.token_generation_buckets,
        async_mode=args.async_mode,
        speculation_length=args.speculation_length,
        enable_fused_speculation=args.enable_fused_speculation,
        enable_eagle_speculation=args.enable_eagle_speculation,
        is_eagle3=args.is_eagle3,
        is_medusa=args.is_medusa,
        num_medusa_heads=args.num_medusa_heads,
        medusa_tree=_load_json_arg(args.medusa_tree),
        quantized=args.quantized,
        quantization_dtype=args.quantization_dtype,
        quantization_type=args.quantization_type,
        quantized_checkpoints_path=args.quantized_checkpoints_path,
        activation_quantization_type=args.activation_quantization_type,
        quantize_clamp_bound=args.quantize_clamp_bound,
        kv_cache_quant=args.kv_cache_quant,
        kv_quant_config=(
            (
                {"dtype": args.kv_quant_dtype,
                 "scale_mode": args.kv_scale_mode,
                 "scales_path": args.kv_scales_path}
                if args.kv_scale_mode in ("per_key", "per_channel")
                else {"dtype": args.kv_quant_dtype,
                      "scale_mode": args.kv_scale_mode,
                      "k_scale": args.k_scale, "v_scale": args.v_scale}
                if args.kv_scale_mode == "per_tensor"
                # direct_cast still honors --kv-quant-dtype (fp8/int8 store)
                else {"dtype": args.kv_quant_dtype,
                      "scale_mode": "direct_cast"}
            )
            if args.kv_cache_quant
            else None
        ),
        token_tree_config=(
            {"dynamic": {"steps": args.dynamic_tree_steps,
                         "branching_factor": args.dynamic_tree_branching,
                         "num_inputs": args.dynamic_tree_num_inputs}}
            if args.dynamic_tree_steps
            else _load_json_arg(args.token_tree_config)
        ),
        skip_warmup=args.skip_warmup,
        lora_config=lora_cfg,
        **({"long_context_mode": bool(args.long_context_mode)}
           if args.long_context_mode is not None else {}),
        **({"vocab_parallel": bool(args.vocab_parallel)}
           if args.vocab_parallel is not None else {}),
    )


def _load_json_arg(arg):
    """File-or-inline JSON (token trees, LoRA path maps, tolerance maps)."""
    if not arg:
        return None
    import os

    if os.path.exists(arg):
        with open(arg) as f:
            return json.load(f)
    return json.loads(arg)


def _resolve_input_ids(args, max_ctx: int) -> np.ndarray:
    """Tokenize/parse prompts; enforce --max-context-length BEFORE any model
    build so an over-long prompt fails (or truncates) at zero compile cost.
    Truncation keeps each row's LEADING real tokens, like the reference's
    head-negative ``F.pad`` (model_wrapper.py:766) — identical commands
    must produce identical prompts across stacks (applied per row, before
    the batch right-pad)."""

    def truncate_rows(rows):
        lens = [len(r) for r in rows]
        if max(lens) <= max_ctx:
            return rows
        if not args.allow_input_truncation:
            raise ValueError(
                f"prompt length {max(lens)} exceeds max_context_length "
                f"{max_ctx}; pass --allow-input-truncation to keep each "
                "prompt's leading tokens"
            )
        return [r[:max_ctx] for r in rows]

    if args.input_ids:
        rows = truncate_rows([list(r) for r in json.loads(args.input_ids)])
        width = max(len(r) for r in rows)
        out = np.full((len(rows), width), args.pad_token_id, dtype=np.int64)
        for i, r in enumerate(rows):
            out[i, : len(r)] = r
        return out
    prompts = args.prompt or ["I believe the meaning of life is"]
    from transformers import AutoTokenizer

    tok = AutoTokenizer.from_pretrained(args.model_path)
    if tok.pad_token_id is None:
        tok.pad_token = tok.eos_token
    enc = tok(prompts, return_tensors=None)["input_ids"]
    rows = truncate_rows([list(r) for r in enc])
    args._tokenizer = tok
    width = max(len(r) for r in rows)
    out = np.full((len(rows), width), tok.pad_token_id, dtype=np.int64)
    for i, r in enumerate(rows):
        out[i, : len(r)] = r
    return out


def run_inference(args) -> int:
    """Compile -> load -> accuracy -> generate -> benchmark
    (reference: inference_demo.py:495)."""
    if args.xla_flags:
        import os

        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "") + " " + args.xla_flags
        ).strip()
    if args.on_cpu:
        import jax

        jax.config.update("jax_platforms", "cpu")

    from nxdi_tpu.generation.hf_adapter import (
        HuggingFaceGenerationAdapter,
        load_pretrained_config,
    )
    from nxdi_tpu.models.registry import get_family
    from nxdi_tpu.runtime.application import TpuModelForCausalLM

    family, cfg_cls = get_family(args.model_type)
    tpu_config = create_tpu_config(args)
    config = cfg_cls(tpu_config, load_config=load_pretrained_config(args.model_path))

    # resolve + length-check prompts BEFORE any model build: an over-long
    # prompt must fail (or truncate, per row) at zero compile cost
    input_ids = _resolve_input_ids(args, tpu_config.max_context_length)

    wants_spec = (
        args.enable_fused_speculation
        or args.enable_eagle_speculation
        or (args.speculation_length > 0 and not args.is_medusa)
    )
    if wants_spec and not args.draft_model_path:
        raise ValueError(
            "speculative decoding flags (--speculation-length/--enable-fused-"
            "speculation/--enable-eagle-speculation) require --draft-model-path "
            "(there is no draft model to speculate with)"
        )
    if wants_spec:
        # draft config surgery (reference: inference_demo.py:502-537)
        app = _build_spec_app(args, family, config)
    elif args.is_medusa:
        from nxdi_tpu.speculation import MedusaCausalLM

        app = MedusaCausalLM(args.model_path, config, model_family=family)
    else:
        app_cls = getattr(family, "APPLICATION_CLS", TpuModelForCausalLM)
        app = app_cls(args.model_path, config, model_family=family)
    if args.compiled_model_path and not args.skip_compile:
        app.compile(args.compiled_model_path)
    app.load(args.compiled_model_path)
    if args.input_capture_save_dir:
        from nxdi_tpu.utils.snapshot import attach_snapshot_hooks

        attach_snapshot_hooks(app, args.input_capture_save_dir)
    adapter = HuggingFaceGenerationAdapter(app)

    gen_kwargs = dict(
        max_new_tokens=args.max_new_tokens,
        do_sample=args.do_sample,
        top_k=args.top_k,
        top_p=args.top_p,
        temperature=args.temperature,
        pad_token_id=args.pad_token_id,
        seed=args.seed,
    )
    if args.enable_lora and args.adapter_id:
        if len(args.adapter_id) != input_ids.shape[0]:
            raise ValueError(
                f"--adapter-id count ({len(args.adapter_id)}) must match the "
                f"prompt count ({input_ids.shape[0]})"
            )
        gen_kwargs["adapter_ids"] = np.array(
            [app.lora_adapter_id(None if a in ("base", "none") else a)
             for a in args.adapter_id],
            dtype=np.int32,
        )

    rc = 0
    if args.check_accuracy_mode != "skip":
        rc = _run_accuracy(args, app, adapter, input_ids)

    outputs = adapter.generate(input_ids, **gen_kwargs)
    tok = getattr(args, "_tokenizer", None)
    print("Generated outputs:")
    for i, row in enumerate(outputs):
        if tok is not None:
            print(f"Output {i}: {tok.decode([t for t in row if t != args.pad_token_id])}")
        else:
            print(f"Output {i}: {row.tolist()}")

    if args.benchmark:
        from nxdi_tpu.utils.benchmark import BENCHMARK_REPORT_FILENAME, benchmark_sampling

        report = benchmark_sampling(
            adapter,
            input_ids,
            args.max_new_tokens,
            n_runs=args.num_runs,
            report_path=BENCHMARK_REPORT_FILENAME,
            **{k: v for k, v in gen_kwargs.items() if k != "max_new_tokens"},
        )
        print("Benchmark completed and its result is as following")
        print(json.dumps(report, indent=2))
    return rc


def _build_spec_app(args, family, config):
    """Fused / EAGLE speculation application construction (reference: draft
    model config surgery inference_demo.py:502-537)."""
    from nxdi_tpu.config import TpuConfig
    from nxdi_tpu.generation.hf_adapter import load_pretrained_config
    from nxdi_tpu.models.registry import get_family
    from nxdi_tpu.speculation import EagleSpecCausalLM, FusedSpecCausalLM

    draft_tpu = TpuConfig(
        **{
            **{k: v for k, v in config.tpu_config.to_dict().items()
               if k not in ("speculation_config", "speculation_length",
                            "enable_fused_speculation", "enable_eagle_speculation")},
            "is_eagle3": args.is_eagle3,
            # unfused speculation may run the draft at a smaller tp than the
            # target (reference: draft_model_tp_degree)
            **({"tp_degree": args.draft_model_tp_degree}
               if args.draft_model_tp_degree else {}),
        }
    )
    if (args.draft_model_tp_degree
            and args.draft_model_tp_degree != config.tpu_config.tp_degree
            and (args.enable_fused_speculation or args.enable_eagle_speculation)):
        raise ValueError(
            "--draft-model-tp-degree requires unfused speculation (the fused "
            "one-graph window shares the target's mesh)"
        )
    if args.enable_eagle_speculation:
        from nxdi_tpu.models import llama_eagle

        dcfg = llama_eagle.LlamaEagleInferenceConfig(
            draft_tpu, load_config=load_pretrained_config(args.draft_model_path)
        )
        return EagleSpecCausalLM(
            args.model_path, config, args.draft_model_path, dcfg, model_family=family
        )
    d_family, d_cfg_cls = get_family(args.draft_model_type or args.model_type)
    dcfg = d_cfg_cls(
        draft_tpu, load_config=load_pretrained_config(args.draft_model_path)
    )
    if args.enable_fused_speculation:
        return FusedSpecCausalLM(
            args.model_path, config, args.draft_model_path, dcfg,
            model_family=family, draft_family=d_family,
        )
    from nxdi_tpu.speculation import StandardSpecCausalLM

    return StandardSpecCausalLM(
        args.model_path, config, args.draft_model_path, dcfg,
        model_family=family, draft_family=d_family,
    )


def _run_accuracy(args, app, adapter, input_ids) -> int:
    """HF CPU golden accuracy checks (reference: inference_demo.py:712)."""
    from transformers import AutoModelForCausalLM

    from nxdi_tpu.utils import accuracy
    from nxdi_tpu.utils.exceptions import AccuracyValidationError, LogitMatchingValidationError

    tol_map = None
    if args.tol_map:
        tol_map = {int(k): float(v) for k, v in _load_json_arg(args.tol_map).items()}

    expected = None
    if args.expected_outputs_path:
        # saved golden tokens replace the HF CPU run (reference:
        # --expected-outputs-path)
        if args.expected_outputs_path.endswith(".npz"):
            expected = np.load(args.expected_outputs_path)["tokens"]
        else:
            with open(args.expected_outputs_path) as f:
                expected = np.asarray(json.load(f), dtype=np.int64)

    hf_model = None
    if expected is None or args.check_accuracy_mode == "logit-matching":
        logger.info("loading HF golden model on CPU for accuracy check")
        hf_model = AutoModelForCausalLM.from_pretrained(args.model_path).eval()
    checked_ids = input_ids  # the sequence the failing check actually ran on
    try:
        if args.check_accuracy_mode == "token-matching":
            accuracy.check_accuracy(
                adapter,
                input_ids,
                args.max_new_tokens,
                hf_model=hf_model,
                expected_outputs=expected,
                pad_token_id=args.pad_token_id,
            )
            print("Accuracy check (token-matching): PASS")
        else:
            golden = (
                expected if expected is not None
                else accuracy.hf_greedy_generate(hf_model, input_ids, args.max_new_tokens)
            )
            if args.num_tokens_to_check is not None:
                golden = golden[:, : input_ids.shape[1] + args.num_tokens_to_check]
            checked_ids = golden
            errors = accuracy.check_accuracy_logits(
                app,
                golden,
                hf_model=hf_model,
                divergence_difference_tol=args.divergence_difference_tol,
                tol_map=tol_map,
            )
            print(
                f"Accuracy check (logit-matching): PASS "
                f"(max err {max(errors.values()):.6f} over {len(errors)} positions)"
            )
        return 0
    except (AccuracyValidationError, LogitMatchingValidationError) as e:
        print(f"Accuracy check FAILED: {e}")
        if args.capture_output_dir and isinstance(e, LogitMatchingValidationError):
            from nxdi_tpu.utils.debug import capture_inputs_at_divergence

            res = capture_inputs_at_divergence(
                app, checked_ids, args.capture_output_dir, hf_model=hf_model,
                divergence_difference_tol=args.divergence_difference_tol,
                divergence_index=e.divergence_index,
                errors_by_index=e.errors_by_index,
            )
            print(f"Divergence bundle written: {res['path']}")
        return 1


def main(argv: Optional[List[str]] = None) -> int:
    parser = argparse.ArgumentParser(prog="inference_demo")
    sub = parser.add_subparsers(dest="command")
    run_parser = sub.add_parser("run", help="compile, load and run a model")
    setup_run_parser(run_parser)
    args = parser.parse_args(argv)
    if args.command != "run":
        parser.print_help()
        return 2
    logging.basicConfig(level=logging.DEBUG if args.verbose else logging.INFO)
    return run_inference(args)


if __name__ == "__main__":
    sys.exit(main())
