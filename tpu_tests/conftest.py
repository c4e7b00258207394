"""TPU-backend correctness tier — runs against the REAL chip.

The reference's main device-backend oracle is rerunning the op suite under
the accelerator context and cross-comparing with CPU
([U:tests/python/gpu/test_operator_gpu.py] + check_consistency).  This
tier is the TPU analog.  It is intentionally OUTSIDE tests/ (whose
conftest pins everything to a virtual CPU mesh):

    MXNET_TEST_CTX=tpu python -m pytest tpu_tests/ -q

The chip is reached only through the chip tool, one process per chip.
Without ``MXNET_TEST_CTX=tpu`` the tier is skipped wholesale (plain
``pytest`` over the repo must not go for a chip); WITH it, finding no
accelerator is an error — an opted-in tier that skips and exits 0 would
pass for a green chip run.
"""
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def pytest_collection_modifyitems(config, items):
    if os.environ.get("MXNET_TEST_CTX") != "tpu":
        skip = pytest.mark.skip(reason="set MXNET_TEST_CTX=tpu to run the real-chip tier")
        for item in items:
            item.add_marker(skip)
        return
    import jax

    if not any(d.platform != "cpu" for d in jax.local_devices()):
        raise pytest.UsageError(
            "MXNET_TEST_CTX=tpu but JAX holds no accelerator: "
            f"jax.local_devices() = {jax.local_devices()}")
