#!/usr/bin/env python
"""DEPRECATED shim — the analytic FLOP accounting moved into
``tools/compile_report.py --analytic`` (one CLI surface for all compile
cost accounting: registry dumps, xplane device tables, and this analytic
bench-config table).  This entry point stays so existing invocations and
PERF_NOTES recipes keep working:

    JAX_PLATFORMS=cpu python tools/flops_report.py

is now exactly

    JAX_PLATFORMS=cpu python tools/compile_report.py --analytic
"""
import os
import sys
import warnings

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from compile_report import analytic_report  # noqa: E402
from compile_report import _build, _fwd_flops_per_sample  # noqa: F401,E402


def main():
    warnings.warn(
        "tools/flops_report.py is deprecated; use "
        "tools/compile_report.py --analytic", DeprecationWarning,
        stacklevel=2)
    return analytic_report()


if __name__ == "__main__":
    sys.exit(main())
