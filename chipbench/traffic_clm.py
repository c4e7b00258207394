"""Causal-LM batches for the ``train_steps`` driver: token ids drawn from a
Zipf law over the chip's vocabulary slice, labels the next token.

``traffic_gen.train_batch`` makes BERT's four arrays; a decoder needs two.
Every seed draws from the same law, so runs with different seeds do the same
amount of work up to what routing makes of the ids.
"""
from __future__ import annotations

import numpy as np

from .traffic_gen import _rng


def zipf_ids(rng, n, vocab, exponent):
    """``n`` ids in ``[0, vocab)``, id ``r`` with probability ∝ (r+1)^-exponent."""
    weights = 1.0 / np.arange(1, vocab + 1, dtype=np.float64) ** float(exponent)
    cdf = np.cumsum(weights / weights.sum())
    return np.minimum(np.searchsorted(cdf, rng.random_sample(n)), vocab - 1)


def clm_batch(traffic, seed, vocab, chips):
    """One batch from ``--seed``: token ids ``[B, S]`` and the labels, each
    position's next token (the last label is one more draw): no padding."""
    spec = traffic["ids"]
    if spec["dist"] != "zipf":
        raise ValueError(f"unknown id distribution {spec['dist']!r}")
    b = int(traffic["per_chip_batch"]) * chips
    s = int(traffic["seq_length"])
    ids = zipf_ids(_rng(seed), b * (s + 1), vocab, spec["exponent"])
    ids = ids.reshape(b, s + 1).astype(np.int32)
    return ids[:, :-1].copy(), ids[:, 1:].copy()
