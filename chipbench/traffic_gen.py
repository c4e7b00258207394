"""The one traffic generator: a traffic file's parameters -> requests.

Every seed gets the SAME multiset of request sizes and inter-arrival gaps,
in another order: the sizes and gaps are drawn from ``POPULATION_SEED`` and
the run's ``--seed`` only permutes them and draws the token ids.  So runs
with different seeds do the same amount of work.

``poisson_arrivals`` and the due-time discipline (lag charged to the
request) are copied from ``benchmark/opperf/generation.py``.
"""
from __future__ import annotations

import numpy as np

POPULATION_SEED = 0


def _rng(seed):
    # --seed may exceed 2**31; RandomState takes up to 2**32 - 1
    return np.random.RandomState(int(seed) % (2 ** 32))


def lengths(spec, n, rng):
    """``n`` integer lengths from ``spec``: ``{"dist": "lognormal", "median",
    "sigma", "min", "max"}``."""
    if spec["dist"] == "lognormal":
        raw = rng.lognormal(np.log(spec["median"]), spec["sigma"], n)
        return np.clip(np.round(raw), spec["min"], spec["max"]).astype(np.int64)
    raise ValueError(f"unknown length distribution {spec['dist']!r}")


def gaps(spec, n, rng):
    """``n`` inter-arrival gaps in seconds, mean ``1 / rate_rps``.  Gamma
    with coefficient of variation ``cv``: ``cv`` 1 is a Poisson process,
    above 1 is burstier."""
    cv = float(spec.get("cv", 1.0))
    shape = 1.0 / (cv * cv)
    return rng.gamma(shape, 1.0 / (spec["rate_rps"] * shape), n)


def requests(traffic, seed, n, vocab, first_id=3):
    """``n`` requests as dicts ``{"prompt": int32 array, "max_new": int,
    "due_s": float | None}``.  ``due_s`` counts from the start of arrivals
    and is None for a closed loop."""
    pop = _rng(POPULATION_SEED)
    src = lengths(traffic["source_length"], n, pop)
    out_spec = traffic["new_tokens"]
    new = np.clip(np.round(out_spec["ratio"] * src), out_spec["min"],
                  out_spec["max"]).astype(np.int64)
    arrivals = traffic["arrivals"]
    gap = gaps(arrivals, n, pop) if arrivals["kind"] == "open" else None
    rng = _rng(seed)
    order = rng.permutation(n)
    src, new = src[order], new[order]
    due = None if gap is None else np.cumsum(gap[rng.permutation(n)])
    return [{"prompt": rng.randint(first_id, vocab, int(src[i])).astype(np.int32),
             "max_new": int(new[i]),
             "due_s": None if due is None else float(due[i])}
            for i in range(n)]


def train_batch(traffic, seed, vocab, chips):
    """One pretraining batch from ``--seed``: token ids, segment ids, the
    sorted masked positions of each sequence and their labels."""
    rng = _rng(seed)
    b = int(traffic["per_chip_batch"]) * chips
    s, p = int(traffic["seq_length"]), int(traffic["masked_positions"])
    tok = rng.randint(0, vocab, (b, s)).astype(np.int32)
    seg = np.zeros((b, s), np.int32)
    pos = np.sort(np.stack([rng.choice(s, p, replace=False) for _ in range(b)]),
                  axis=1).astype(np.int32)
    labels = rng.randint(0, vocab, (b, p)).astype(np.int32)
    return tok, seg, pos, labels
