"""Backlog: every request due at t = 0. First one request per slot whose
output length is spread over ``first_wave_output_len`` (so the slots do not
finish in step), then ``requests_per_second x seconds`` ordinary ones. The
sizes are fixed multisets; the run's seed pairs and orders them and draws the
token ids (see traffic_gen)."""

import numpy as np

from benchmark.traffic_gen import Offer, order_rng, shuffled_sizes, token_ids


def generate(params, seed, seconds, vocab, slots):
    rng, order = np.random.default_rng(seed), order_rng(seed)
    first = shuffled_sizes(params, slots, order, "first_wave_output_len")
    rest = shuffled_sizes(params, int(round(params["requests_per_second"] * seconds)), order)
    return [Offer(0.0, token_ids(rng, p, vocab), int(o)) for p, o in np.concatenate([first, rest])]
