"""PR 31: the two forms of ONE expert layer side by side on the chip, at the routed cell's widths
(hidden 7680, expert intermediate 2048, router 256 wide, top 8, bf16), pinned through
``MoEArch.dispatch``, over the held experts and the rows that ``ops/moe.py expert_form``'s two
conditions speak of: (a) rows x top_k >= num_experts, (b) experts_here <= 2 x top_k.

    python3 scripts/chip_calls/pr31_form_sweep.py [--out chiprun_out/pr31/sweep.json]

Prints one line a point: median ms of the layer in each form (a jitted ``moe_block`` on random
weights and rows, no shared expert, the weights an argument read in place), what the rule chooses
there, and the held weights' bytes over the dense form's time as a share of the chip's 819 GB/s.
"""
import argparse
import dataclasses
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

import jax
import jax.numpy as jnp

from nxdi_tpu.ops.moe import MoEArch, expert_form, moe_block

H, I, E, K = 7680, 2048, 256, 8
HBM_GBS = 819.0  # v5e, as benchmark/costs.py has it


def layer_ms(moe, params, x, repeats=15):
    fn = jax.jit(lambda p, x: moe_block(None, moe, p, x))
    fn(params, x).block_until_ready()
    times = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        fn(params, x).block_until_ready()
        times.append((time.perf_counter() - t0) * 1e3)
    return statistics.median(times)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="chiprun_out/pr31/sweep.json")
    ap.add_argument("--held", default="8,16,24,32")
    ap.add_argument("--rows", default="16,32,128,1024")
    args = ap.parse_args()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"needs the chip, found {dev.platform}")
    points = []
    for held in map(int, args.held.split(",")):
        moe = MoEArch(num_experts=E, top_k=K, intermediate_size=I, sigmoid_routing=True,
                      routed_scaling=2.5, norm_topk_prob=True, held_experts=held, first_held=0)
        keys = jax.random.split(jax.random.PRNGKey(held), 5)
        w = lambda k, *s: (jax.random.normal(k, s, jnp.float32) * 0.02).astype(jnp.bfloat16)  # noqa: E731
        params = {
            "router": {"w": w(keys[0], H, E)},
            "experts": {"gate_proj": {"w": w(keys[1], held, H, I)}, "up_proj": {"w": w(keys[2], held, H, I)},
                        "down_proj": {"w": w(keys[3], held, I, H)}},
        }
        weight_bytes = 3 * held * H * I * 2
        for rows in map(int, args.rows.split(",")):
            x = jax.random.normal(keys[4], (rows, 1, H), jnp.float32).astype(jnp.bfloat16)
            ms = {form: layer_ms(dataclasses.replace(moe, dispatch=form), params, x) for form in ("dense", "sorted")}
            point = {
                "held": held, "rows": rows, "dense_ms": ms["dense"], "sorted_ms": ms["sorted"],
                "rule": expert_form(moe, rows),
                "dense_hbm_pct": 100 * weight_bytes / (ms["dense"] * 1e-3) / (HBM_GBS * 1e9),
                "sorted_hbm_pct": 100 * weight_bytes / (ms["sorted"] * 1e-3) / (HBM_GBS * 1e9),
            }
            points.append(point)
            print(json.dumps(point), flush=True)
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump({"device": dev.device_kind, "points": points}, f, indent=1)


if __name__ == "__main__":
    main()
