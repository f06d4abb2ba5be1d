"""What every traffic generator shares: an offer, and draws that give every
seed the SAME work in ANOTHER order.

A generator is a file ``generators/<name>.py`` with
``generate(params, seed, seconds, vocab, slots) -> [Offer]`` (sorted by due
time); a traffic mix is a data file ``traffic/<name>.json`` naming its
generator and parameters. Sizes and gaps are quantile grids of the stated
distributions: one fixed multiset of prompt lengths, output lengths and
inter-arrival gaps per (file, window length), so every run serves the same
tokens. The run's ``--seed`` draws how they are paired, the order they arrive
in, the token ids (and the weights): which request comes when, and behind
which other, differs from seed to seed.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from statistics import NormalDist
from typing import List

import numpy as np


@dataclass
class Offer:
    due_s: float  # seconds after the loop starts
    prompt: List[int]
    max_new: int


def length_grid(spec: dict, n: int) -> np.ndarray:
    """``n`` whole lengths at the quantiles (i + 0.5) / n of ``spec``'s
    distribution, ascending, clipped to its ``lo``..``hi`` — the same for
    every seed."""
    q = (np.arange(n) + 0.5) / n
    if spec["dist"] == "uniform":
        vals = np.floor(spec["lo"] + q * (spec["hi"] + 1 - spec["lo"]))
    elif spec["dist"] == "lognormal":
        z = np.array([NormalDist().inv_cdf(float(x)) for x in q])
        vals = np.round(np.exp(math.log(spec["median"]) + spec["sigma"] * z))
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return np.clip(vals, spec["lo"], spec["hi"]).astype(np.int64)


def max_prompt_len(params: dict) -> int:
    return int(params["prompt_len"]["hi"])


def order_rng(seed: int) -> np.random.Generator:
    """The stream that pairs and orders a run's sizes and gaps (apart from the
    one that draws its token ids, so the two do not shift each other)."""
    return np.random.default_rng([int(seed), 1])


def shuffled_sizes(params: dict, n: int, rng: np.random.Generator,
                   out_key: str = "output_len") -> np.ndarray:
    """``(n, 2)`` of (prompt length, output length): both grids, each in an
    order of its own drawn from ``rng`` — the same two multisets whatever the
    seed, paired and ordered anew by each."""
    prompts = length_grid(params["prompt_len"], n)
    outs = length_grid(params[out_key], n)
    return np.stack([rng.permutation(prompts), rng.permutation(outs)], axis=1)


def exponential_gaps(rate_per_s: float, n: int) -> np.ndarray:
    """``n`` inter-arrival gaps at the quantiles of Exp(rate): one fixed
    multiset. A seed's permutation of it is its arrival stream: exponential
    gaps as of a Poisson process, but the same number of arrivals in every
    window (a Poisson count would vary by its square root)."""
    q = (np.arange(n) + 0.5) / n
    return -np.log1p(-q) / rate_per_s


def token_ids(rng: np.random.Generator, length: int, vocab: int) -> List[int]:
    return rng.integers(0, vocab, size=int(length)).tolist()
