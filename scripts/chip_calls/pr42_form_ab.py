"""PR 42: the three forms of ONE Solar-Open2 expert layer side by side on the chip, pinned through
``MoEArch.dispatch``: 40 of 320 experts held, top 8 by sigmoid scores + a selection bias, renormalised,
hidden 4096, expert intermediate 1280, bf16 (`solar-open2-ep8`'s share), the router and the experts drawn
as the harness draws them (normal x 0.02, the selection bias among the weights), rows normal (a normed
hidden state). Then the same layer three times inside a ``lax.scan`` over layer-stacked weights, as the
KDA segment runs it: the dense form's weights in the scan's xs, the sorted and the compact form's held out
of the xs and indexed by layer (``models/base.py _extract_stacked_weights``).

    python3 scripts/chip_calls/pr42_form_ab.py [--out chiprun_out/pr42/ab.json] [--rows 16,40,..]

Prints one line a point: median ms of the layer (or the scan) in each form, what ``expert_form`` chooses
there, the held pairs, the held weights' bytes over each form's time as a share of the chip's 819 GB/s,
and each form's largest difference from the dense form's output.
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

from nxdi_tpu.analysis.costs import resolve_chip
from nxdi_tpu.ops.moe import EXPERT_FORMS, MoEArch, expert_form, moe_block

H, I, E, K = 4096, 1280, 320, 8
HBM_GBS = 819.0  # v5e, as benchmark/costs.py has it
WEIGHT_STD = 0.02  # benchmark/serving_app.py's draw


def share(held):
    return MoEArch(num_experts=E, top_k=K, intermediate_size=I, sigmoid_routing=True, correction_bias=True,
                   norm_topk_prob=True, held_experts=held, first_held=0,
                   ridge_rows=resolve_chip().ridge_rows)  # the attached chip's, as a share's arch resolves it


def draw(key, held, layers=None):
    """The router and the held experts, layer-stacked when ``layers`` is given."""
    lead = () if layers is None else (layers,)
    ks = jax.random.split(key, 5)
    w = lambda k, *s: (jax.random.normal(k, lead + s, jnp.float32) * WEIGHT_STD).astype(jnp.bfloat16)  # noqa: E731
    return {
        "router": {"w": w(ks[0], H, E),
                   "e_bias": jax.random.normal(ks[4], lead + (E,), jnp.float32) * WEIGHT_STD},
        "experts": {"gate_proj": {"w": w(ks[1], held, H, I)}, "up_proj": {"w": w(ks[2], held, H, I)},
                    "down_proj": {"w": w(ks[3], held, I, H)}},
    }


def timed(fn, *args, repeats=20):
    out = fn(*args)
    out.block_until_ready()
    times = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        fn(*args).block_until_ready()
        times.append((time.perf_counter() - t0) * 1e3)
    return statistics.median(times), out


def held_pairs(moe, params, x):
    tally = []
    moe_block(None, dataclasses.replace(moe, dispatch="dense"), params, x, held_tally=tally)
    return tally[0][0]


def point(moe, params, x, rows, label, fns, weight_bytes):
    ms, outs = {}, {}
    for form in EXPERT_FORMS:
        ms[form], outs[form] = timed(fns[form], params, x)
    ref = outs["dense"].astype(jnp.float32)
    p = {"point": label, "held": moe.held_experts, "rows": rows, "rule": expert_form(moe, rows),
         **{f"{f}_ms": round(ms[f], 4) for f in EXPERT_FORMS},
         **{f"{f}_hbm_pct": round(100 * weight_bytes / (ms[f] * 1e-3) / (HBM_GBS * 1e9), 1) for f in EXPERT_FORMS},
         **{f"{f}_maxdiff": float(jnp.max(jnp.abs(outs[f].astype(jnp.float32) - ref))) for f in ("sorted", "compact")},
         "out_absmax": float(jnp.max(jnp.abs(ref)))}
    print(json.dumps(p), flush=True)
    return p


def one_layer(held, rows_list):
    moe = share(held)
    params = draw(jax.random.PRNGKey(held), held)
    weight_bytes = 3 * held * H * I * 2
    fns = {f: jax.jit(lambda p, x, m=dataclasses.replace(moe, dispatch=f): moe_block(None, m, p, x))
           for f in EXPERT_FORMS}
    points = []
    for rows in rows_list:
        x = jax.random.normal(jax.random.PRNGKey(rows), (rows, 1, H), jnp.float32).astype(jnp.bfloat16)
        p = point(moe, params, x, rows, "layer", fns, weight_bytes)
        p["held_pairs"] = int(jax.jit(held_pairs, static_argnums=0)(moe, params, x))
        points.append(p)
    return points


def scan_fn(moe, form, layers):
    """``layers`` expert layers in one scan over the stacked weights, each adding to the residual."""
    m = dataclasses.replace(moe, dispatch=form)
    names = ("gate_proj", "up_proj", "down_proj")

    def run(params, x):
        seg = params
        stacked = None
        if form != "dense":  # held out of the xs, indexed by layer
            stacked = tuple(params["experts"][k]["w"] for k in names)
            seg = {"router": params["router"], "experts": {k: {} for k in names}}

        def body(h, xs):
            lp, li = xs
            st = None if stacked is None else (*stacked, li)
            return h + moe_block(None, m, lp, h, stacked_experts=st), None

        h, _ = jax.lax.scan(body, x, (seg, jnp.arange(layers, dtype=jnp.int32)))
        return h

    return jax.jit(run)


def in_a_scan(held, rows_list, layers=3):
    moe = share(held)
    params = draw(jax.random.PRNGKey(held + 1), held, layers)
    weight_bytes = layers * 3 * held * H * I * 2
    fns = {f: scan_fn(moe, f, layers) for f in EXPERT_FORMS}
    points = []
    for rows in rows_list:
        x = jax.random.normal(jax.random.PRNGKey(rows + 1), (rows, 1, H), jnp.float32).astype(jnp.bfloat16)
        points.append(point(moe, params, x, rows, f"scan{layers}", fns, weight_bytes))
    return points


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="chiprun_out/pr42/ab.json")
    ap.add_argument("--rows", default="16,40,128,256,512,1024")
    ap.add_argument("--scan-rows", default="128,256")
    ap.add_argument("--more-held", default="24,32", help="further held counts, at --more-rows")
    ap.add_argument("--more-rows", default="128,256")
    args = ap.parse_args()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"needs the chip, found {dev.platform}")
    ints = lambda s: [int(v) for v in s.split(",") if v]  # noqa: E731
    points = one_layer(40, ints(args.rows)) + in_a_scan(40, ints(args.scan_rows))
    for held in ints(args.more_held):
        points += one_layer(held, ints(args.more_rows))
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump({"device": dev.device_kind, "points": points}, f, indent=1)


if __name__ == "__main__":
    main()
