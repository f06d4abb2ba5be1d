"""Open loop at one fixed rate: ``rate_per_s x seconds`` requests whose gaps
are the quantile grid of the exponential distribution (a Poisson process's
gaps, with a fixed count per window). Sizes and gaps are fixed multisets; the
run's seed pairs and orders them and draws the token ids (see traffic_gen)."""

import numpy as np

from benchmark.traffic_gen import Offer, exponential_gaps, order_rng, shuffled_sizes, token_ids


def generate(params, seed, seconds, vocab, slots):
    rng, order = np.random.default_rng(seed), order_rng(seed)
    n = int(round(params["rate_per_s"] * seconds))
    sizes = shuffled_sizes(params, n, order)
    gaps = order.permutation(exponential_gaps(params["rate_per_s"], n))
    return [
        Offer(float(t), token_ids(rng, p, vocab), int(o))
        for t, (p, o) in zip(np.cumsum(gaps), sizes)
    ]
