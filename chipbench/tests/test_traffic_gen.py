"""Every seed gets the same sizes and gaps, in another order."""
import json
import os

import numpy as np

from chipbench import traffic_gen
from conftest import ROOT


def load(name):
    return json.load(open(os.path.join(ROOT, "chipbench", "traffic", name + ".json")))


def test_seeds_permute_one_population():
    traffic = load("translate-steady")
    a = traffic_gen.requests(traffic, 11, 500, 32768)
    b = traffic_gen.requests(traffic, 3000000011, 500, 32768)
    sizes = lambda rs: sorted((len(r["prompt"]), r["max_new"]) for r in rs)
    assert sizes(a) == sizes(b)
    assert [len(r["prompt"]) for r in a] != [len(r["prompt"]) for r in b]
    gaps = lambda rs: np.sort(np.diff([0.0] + [r["due_s"] for r in rs]))
    np.testing.assert_allclose(gaps(a), gaps(b), rtol=1e-9)
    rate = len(a) / a[-1]["due_s"]
    assert abs(rate / traffic["arrivals"]["rate_rps"] - 1) < 0.15
    assert all(4 <= len(r["prompt"]) <= 128 and 4 <= r["max_new"] <= 128 for r in a)
    assert all(r["prompt"].min() >= 3 for r in a)   # never bos/eos/pad


def test_closed_loop_has_no_due_times_and_train_batch_is_seeded():
    traffic = load("translate-backlog")
    assert all(r["due_s"] is None for r in traffic_gen.requests(traffic, 1, 50, 211))
    t = load("pretrain-s128")
    one = traffic_gen.train_batch(t, 2 ** 31 + 5, 30522, 1)
    two = traffic_gen.train_batch(t, 2 ** 31 + 5, 30522, 1)
    assert all(np.array_equal(x, y) for x, y in zip(one, two))
    tok, seg, pos, labels = one
    assert tok.shape == (64, 128) and pos.shape == (64, 20) and labels.shape == (64, 20)
    assert np.all(np.diff(pos, axis=1) > 0)
