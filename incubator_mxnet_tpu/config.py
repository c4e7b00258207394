"""``mx.config`` — the MXNET_* environment-variable surface.

Parity: the reference reads ~100 ``MXNET_*`` envs at use sites via
``dmlc::GetEnv`` (documented in [U:docs/.../env_var.md]).  Here the
meaningful ones map onto XLA/JAX knobs in ONE place, applied at import
(``apply_env``) so the env contract matches the reference: set the
variable before launching, behavior changes globally.

================================  ============================================
env var                           effect (TPU-native mapping)
================================  ============================================
MXNET_ENGINE_TYPE                 NaiveEngine → ``jax.config jax_disable_jit``
                                  (synchronous debug mode; engine.py parity)
MXNET_GPU_MEM_POOL_RESERVE        percent reserved → XLA client mem fraction
                                  (1 - reserve/100) via
                                  ``XLA_PYTHON_CLIENT_MEM_FRACTION``
MXNET_GPU_MEM_POOL_TYPE           ``Naive`` → ``XLA_PYTHON_CLIENT_ALLOCATOR=
                                  platform`` (no BFC pool); ``Round`` is the
                                  default BFC behavior
MXNET_CPU_WORKER_NTHREADS         host compute threads →
                                  ``--xla_cpu_multi_thread_eigen`` thread pool
                                  via ``XLA_FLAGS`` (best effort, pre-backend)
MXNET_EXEC_BULK_EXEC_MAX_NODE_TRAIN  engine bulking limit (engine.py)
MXNET_PROFILER_AUTOSTART          1 → start an xprof trace at import
                                  (profiler.py)
MXNET_ENFORCE_DETERMINISM         1 → ``jax_threefry_partitionable`` off +
                                  deterministic reductions where offered
MXNET_TPU_FLASH                   flash-attention dispatch (ops/attention.py):
                                  ``auto`` (kernels on a TPU) and ``on``
                                  (compiled wherever the call lands) choose
                                  by shape at the measured crossover, which
                                  has no override; ``off``; ``interpret``
MXNET_TPU_MATMUL_PRECISION        fp32 matmul precision (package __init__)
MXNET_TPU_PRNG                    PRNG impl: ``rbg`` (default — hardware
                                  RNG, +11% BERT step, PERF_NOTES) or
                                  ``threefry`` (JAX default; also implied
                                  by MXNET_ENFORCE_DETERMINISM=1)
MXNET_TEST_CTX                    ``tpu`` enables the real-chip test tier
================================  ============================================

``describe()`` prints the live table with current values.

Two helpers every measurement entry point (``chip_smoke.py``, ``bench.py``,
``benchmark/opperf/*``) calls before its first compile:
:func:`enable_compile_cache` and :func:`device_record`.
"""
from __future__ import annotations

import os

__all__ = ["apply_env", "describe", "memory_info", "enable_compile_cache",
           "device_record"]

_APPLIED = {}

# <checkout>/.jax_cache — located from this file, never from cwd, a temp
# dir, a pid or a clock: the directory is part of the cache key, so a
# default that moves between runs never hits
_DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".jax_cache")


def enable_compile_cache():
    """Turn on JAX's persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    this sets no other; otherwise the cache lives at
    ``<checkout>/.jax_cache``.  JAX's own thresholds decide what is worth
    an entry (compiles of a second or more)."""
    import jax

    if "JAX_COMPILATION_CACHE_DIR" not in os.environ:
        jax.config.update("jax_compilation_cache_dir", _DEFAULT_CACHE_DIR)
    return jax.config.jax_compilation_cache_dir


def device_record():
    """Where this process runs, as JAX reports it — the three fields every
    benchmark record carries so no number can pass for a device metric
    without naming its device."""
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "device_kind": devs[0].device_kind,
            "n_devices": len(devs)}


def apply_env():
    """Map MXNET_* envs onto XLA/JAX knobs.  Called from package import;
    idempotent.  Entries that must precede backend creation are best-effort
    (they warn in ``describe()`` if the backend already exists)."""
    if _APPLIED.get("done"):
        return
    _APPLIED["done"] = True

    eng = os.environ.get("MXNET_ENGINE_TYPE")
    if eng == "NaiveEngine":
        import jax

        jax.config.update("jax_disable_jit", True)
        _APPLIED["MXNET_ENGINE_TYPE"] = "jax_disable_jit=True"

    reserve = os.environ.get("MXNET_GPU_MEM_POOL_RESERVE")
    if reserve is not None:
        frac = max(0.0, min(1.0, 1.0 - float(reserve) / 100.0))
        os.environ.setdefault("XLA_PYTHON_CLIENT_MEM_FRACTION", f"{frac:.2f}")
        _APPLIED["MXNET_GPU_MEM_POOL_RESERVE"] = \
            f"XLA_PYTHON_CLIENT_MEM_FRACTION={frac:.2f}"

    pool = os.environ.get("MXNET_GPU_MEM_POOL_TYPE")
    if pool and pool.lower() == "naive":
        os.environ.setdefault("XLA_PYTHON_CLIENT_ALLOCATOR", "platform")
        _APPLIED["MXNET_GPU_MEM_POOL_TYPE"] = "XLA_PYTHON_CLIENT_ALLOCATOR=platform"

    nthreads = os.environ.get("MXNET_CPU_WORKER_NTHREADS")
    if nthreads:
        flags = os.environ.get("XLA_FLAGS", "")
        if "intra_op_parallelism" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + f" --xla_cpu_multi_thread_eigen=true"
                        f" intra_op_parallelism_threads={nthreads}").strip()
        _APPLIED["MXNET_CPU_WORKER_NTHREADS"] = f"XLA_FLAGS threads={nthreads}"

    if os.environ.get("MXNET_ENFORCE_DETERMINISM") == "1":
        import jax

        jax.config.update("jax_threefry_partitionable", False)
        _APPLIED["MXNET_ENFORCE_DETERMINISM"] = "threefry sequential"

    # Hardware PRNG by default: threefry computes its bits in the loop
    # fusions and costs ~10% of a BERT-base training step on v5e (measured
    # 1236.8 → 1355.6 samples/s flipping this alone — docs/PERF_NOTES.md).
    # rbg is deterministic per key and partitionable; set
    # MXNET_TPU_PRNG=threefry to restore JAX's default (e.g. to reproduce
    # sequences from other JAX programs bit-for-bit).
    # MXNET_ENFORCE_DETERMINISM=1 implies threefry unless MXNET_TPU_PRNG
    # says otherwise — its contract is reference-reproducible sequences,
    # which the sequential-threefry knob above only provides on threefry.
    determinism = os.environ.get("MXNET_ENFORCE_DETERMINISM") == "1"
    prng = os.environ.get("MXNET_TPU_PRNG")
    if prng is None:
        prng = "threefry" if determinism else "rbg"
    if prng not in ("rbg", "threefry", "unsafe_rbg"):
        import warnings

        warnings.warn(f"MXNET_TPU_PRNG={prng!r} is not one of "
                      "rbg/threefry/unsafe_rbg; using rbg")
        prng = "rbg"
    import jax

    # JAX names its default impl "threefry2x32"; MXNET_TPU_PRNG keeps the
    # short spelling
    impl = "threefry2x32" if prng == "threefry" else prng
    jax.config.update("jax_default_prng_impl", impl)
    _APPLIED["MXNET_TPU_PRNG"] = f"jax_default_prng_impl={impl}"


def describe():
    """Human-readable table of honored env vars + current values/effects."""
    rows = []
    for var in ("MXNET_ENGINE_TYPE", "MXNET_GPU_MEM_POOL_RESERVE",
                "MXNET_GPU_MEM_POOL_TYPE", "MXNET_CPU_WORKER_NTHREADS",
                "MXNET_EXEC_BULK_EXEC_MAX_NODE_TRAIN",
                "MXNET_PROFILER_AUTOSTART", "MXNET_ENFORCE_DETERMINISM",
                "MXNET_TPU_FLASH", "MXNET_TPU_MATMUL_PRECISION",
                "MXNET_TPU_PRNG", "MXNET_TEST_CTX"):
        rows.append((var, os.environ.get(var, "<unset>"),
                     _APPLIED.get(var, "")))
    width = max(len(r[0]) for r in rows) + 2
    lines = [f"{'env var':<{width}}{'value':<16}applied effect"]
    for var, val, eff in rows:
        lines.append(f"{var:<{width}}{val:<16}{eff}")
    return "\n".join(lines)


def memory_info(ctx=None):
    """Device memory stats (the pool-stats surface of the reference's
    storage manager, [U:src/storage/pooled_storage_manager.h]) — delegated
    to PJRT: bytes_in_use / peak / limit when the backend reports them."""
    import jax

    if ctx is not None and hasattr(ctx, "_jax_device"):
        devices = [ctx._jax_device()]
    elif ctx is not None and hasattr(ctx, "device_id"):
        from .context import _resolve_jax_device

        devices = [_resolve_jax_device(ctx.device_type, ctx.device_id)]
    else:
        devices = jax.local_devices()
    from . import profiler

    shared = profiler.device_memory_stats(devices)
    out = {}
    for d in devices:
        stats = shared.get(str(d)) or {}
        out[str(d)] = {
            "bytes_in_use": stats.get("bytes_in_use"),
            "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
            "bytes_limit": stats.get("bytes_limit"),
        }
    return out
