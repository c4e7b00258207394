"""Misc util shims (parity: [U:python/mxnet/util.py]).

The reference toggles legacy-vs-numpy shape/array semantics process-wide
(``np_shape``/``np_array``); this framework is numpy-semantics natively (jax
is), so the toggles are tracked flags that always behave as enabled for
computation — kept so reference scripts calling ``mx.npx.set_np()`` etc.
run unchanged.
"""
from __future__ import annotations

import contextlib
import functools

_np_shape = True
_np_array = True


def is_np_shape():
    return _np_shape


def is_np_array():
    return _np_array


@contextlib.contextmanager
def np_shape(active=True):
    global _np_shape
    prev, _np_shape = _np_shape, active
    try:
        yield
    finally:
        _np_shape = prev


@contextlib.contextmanager
def np_array(active=True):
    global _np_array
    prev, _np_array = _np_array, active
    try:
        yield
    finally:
        _np_array = prev


def set_np(shape=True, array=True):
    global _np_shape, _np_array
    _np_shape, _np_array = shape, array


def reset_np():
    set_np(True, True)


def use_np(func):
    @functools.wraps(func)
    def wrapper(*a, **k):
        return func(*a, **k)

    return wrapper


def resolve_platform(x=None):
    """The platform a dispatch will actually execute on: a concrete
    input's device wins (eager op on a CPU-placed array while the default
    backend is tpu, e.g. model init under ``jax.default_device(cpu)``);
    then an active ``jax_default_device`` override; then the default
    backend."""
    import jax

    platform = None
    if x is not None and not isinstance(x, jax.core.Tracer):
        try:
            platform = next(iter(x.devices())).platform
        except Exception:
            platform = None
    if platform is None:
        dd = getattr(jax.config, "jax_default_device", None)
        platform = getattr(dd, "platform", None) or jax.default_backend()
    return platform


def makedirs(d):
    """Recursive mkdir that tolerates existing dirs (parity:
    ``mx.util.makedirs`` — pre-exist_ok-era helper)."""
    import os

    os.makedirs(os.path.expanduser(d), exist_ok=True)


def getenv(name):
    """Read an MXNET_* env var through the C runtime in the reference;
    plain os.environ here (parity: ``mx.util.getenv``)."""
    import os

    return os.environ.get(name)


def setenv(name, value):
    """Parity: ``mx.util.setenv`` (process-wide)."""
    import os

    if value is None:
        os.environ.pop(name, None)
    else:
        os.environ[name] = str(value)


def get_gpu_count():
    """Parity: ``mx.util.get_gpu_count`` — accelerator count on this
    host (TPU chips play the gpu role)."""
    from . import context

    return context.num_tpus() or 0


def get_gpu_memory(dev_id=0):
    """Parity: ``mx.util.get_gpu_memory`` -> (free, total) bytes for the
    accelerator, via the shared ``profiler.device_memory_stats`` probe
    (one memory_stats() parse rule for the whole repo)."""
    import jax

    from . import profiler

    devs = [d for d in jax.devices() if d.platform != "cpu"]
    if not devs:
        raise RuntimeError("no accelerator device visible")
    d = devs[min(dev_id, len(devs) - 1)]
    stats = profiler.device_memory_stats([d]).get(str(d))
    if not stats:
        return (0, 0)
    total = stats["bytes_limit"]
    used = stats["bytes_in_use"]
    return (total - used, total)
