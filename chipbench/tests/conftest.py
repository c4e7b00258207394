"""CPU rehearsals of the harness.  Run by hand, not part of tier-1:

    JAX_PLATFORMS=cpu python -m pytest chipbench/tests -q
"""
import json
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

SECTIONS = ("configs", "workloads", "end_to_end", "per_layer")


def load_bench(with_shelved=False):
    """BENCHMARK.json, and with ``with_shelved`` the cells of
    ``chipbench/shelved/*.json`` merged in as a later PR would merge them."""
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    if with_shelved:
        folder = os.path.join(ROOT, "chipbench", "shelved")
        for name in sorted(os.listdir(folder)):
            extra = json.load(open(os.path.join(folder, name)))
            for section in SECTIONS:
                bench[section] = bench[section] + extra[section]
    return bench
