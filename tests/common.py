"""Shared test helpers (parity: [U:tests/python/unittest/common.py]).

``with_seed`` — reproducible-but-rotating RNG seeds with the seed printed on
failure, the reference's core test idiom."""
import functools
import os
import random as pyrandom

import numpy as np

import incubator_mxnet_tpu as mx


def with_seed(seed=None):
    def deco(fn):
        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            if seed is not None:
                this_seed = seed
            elif "MXNET_TEST_SEED" in os.environ:
                this_seed = int(os.environ["MXNET_TEST_SEED"])
            else:
                this_seed = np.random.randint(0, 2 ** 31)
            np.random.seed(this_seed)
            mx.random.seed(this_seed)
            pyrandom.seed(this_seed)
            try:
                return fn(*args, **kwargs)
            except BaseException:
                print(f"*** test failed with seed {this_seed}: "
                      f"set MXNET_TEST_SEED={this_seed} to reproduce ***")
                raise

        return wrapper

    return deco


def host_spans(trace_dir, prefix):
    """``(line, start, end, name, stats)`` of the ``/host:CPU`` events whose
    name starts with ``prefix`` in the newest ``.xplane.pb`` under
    ``trace_dir``."""
    import glob

    import jax

    files = sorted(glob.glob(os.path.join(
        str(trace_dir), "**", "*.xplane.pb"), recursive=True))
    assert files, f"no xplane under {trace_dir}"
    data = jax.profiler.ProfileData.from_file(files[-1])
    out = []
    for plane in data.planes:
        if plane.name != "/host:CPU":
            continue
        for i, line in enumerate(plane.lines):   # thread names repeat
            for ev in line.events:
                if ev.name.startswith(prefix):
                    out.append((f"{i}:{line.name}", ev.start_ns,
                                ev.start_ns + ev.duration_ns, ev.name,
                                {k: str(v) for k, v in ev.stats}))
    return out


def span_inside(child, parent):
    return (child[0] == parent[0] and parent[1] <= child[1]
            and child[2] <= parent[2])
