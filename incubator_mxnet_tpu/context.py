"""Device contexts: ``mx.cpu()``, ``mx.gpu()``, ``mx.tpu()``.

Parity target: [U:python/mxnet/context.py] (Context objects, ``with ctx:``
scoping, ``num_gpus()``) — extended with ``mx.tpu()`` as a first-class context
per the north-star.  A Context resolves lazily to a concrete ``jax.Device``.
``gpu``/``tpu`` name the i-th accelerator ``jax.local_devices()`` holds
(``mx.gpu()`` aliases the TPU so unmodified ``ctx=mx.gpu()`` scripts run) and
raise ``MXNetError`` when there is no such device: a requested accelerator is
never substituted by the host CPU or by another chip.
"""
from __future__ import annotations

import threading

__all__ = ["Context", "cpu", "gpu", "tpu", "current_context", "num_gpus", "num_tpus", "current_device"]

_DEVTYPE_TO_ID = {"cpu": 1, "gpu": 2, "cpu_pinned": 3, "cpu_shared": 5, "tpu": 6}
_ID_TO_DEVTYPE = {v: k for k, v in _DEVTYPE_TO_ID.items()}

_tls = threading.local()


class Context:
    """A device context.  Parity: ``mxnet.context.Context``.

    Unlike the reference (where a Context selects a CUDA device and an engine
    worker pool), here a Context names a JAX device; XLA/PJRT owns streams,
    memory and scheduling.
    """

    __slots__ = ("device_type", "device_id")

    def __init__(self, device_type, device_id=0):
        if isinstance(device_type, Context):
            device_type, device_id = device_type.device_type, device_type.device_id
        if device_type not in _DEVTYPE_TO_ID:
            raise ValueError(f"unknown device type {device_type!r}")
        self.device_type = device_type
        self.device_id = int(device_id)

    @property
    def device_typeid(self):
        return _DEVTYPE_TO_ID[self.device_type]

    # -- jax resolution ----------------------------------------------------
    def jax_device(self):
        """Resolve to a concrete jax.Device (lazily; cached per process)."""
        return _resolve_jax_device(self.device_type, self.device_id)

    # -- scoping -----------------------------------------------------------
    def __enter__(self):
        stack = getattr(_tls, "stack", None)
        if stack is None:
            stack = _tls.stack = []
        stack.append(self)
        return self

    def __exit__(self, *exc):
        _tls.stack.pop()

    def __eq__(self, other):
        return (
            isinstance(other, Context)
            and self.device_type == other.device_type
            and self.device_id == other.device_id
        )

    def __hash__(self):
        return hash((self.device_type, self.device_id))

    def __repr__(self):
        return f"{self.device_type}({self.device_id})"

    def __str__(self):
        return repr(self)

    def empty_cache(self):
        """Parity: Context.empty_cache (GPU pool release).  XLA owns pooling;
        this is a best-effort hint."""
        import gc

        gc.collect()


_device_cache = {}
_device_lock = threading.Lock()


def _accelerator_devices():
    import jax

    # process-LOCAL: under multi-process (dist kvstore / launch_local.py)
    # eager arrays must land on a device this process can address
    return [d for d in jax.local_devices() if d.platform != "cpu"]


def _resolve_jax_device(device_type, device_id):
    key = (device_type, device_id)
    with _device_lock:
        if key in _device_cache:
            return _device_cache[key]
    import jax

    dev = None
    if device_type == "cpu" or device_type.startswith("cpu_"):
        try:
            cpus = jax.local_devices(backend="cpu")
        except RuntimeError:
            cpus = [d for d in jax.local_devices() if d.platform == "cpu"]
        if cpus:
            dev = cpus[min(device_id, len(cpus) - 1)]
        else:
            # CPU platform absent (accelerator-only build): fall back to default
            dev = jax.local_devices()[0]
    else:
        accel = _accelerator_devices()
        if not 0 <= device_id < len(accel):
            from .base import MXNetError

            raise MXNetError(
                f"{device_type}({device_id}) requested but this process "
                f"holds {len(accel)} accelerator device(s); "
                f"jax.local_devices() = {jax.local_devices()}")
        dev = accel[device_id]
    with _device_lock:
        _device_cache[key] = dev
    return dev


def cpu(device_id=0):
    """Return a CPU context (parity: ``mx.cpu``)."""
    return Context("cpu", device_id)


def gpu(device_id=0):
    """Return an accelerator context.  On a TPU image this aliases the TPU so
    that unmodified ``ctx=mx.gpu()`` scripts run (north-star drop-in goal)."""
    return Context("gpu", device_id)


def tpu(device_id=0):
    """Return a TPU context — the north-star first-class context."""
    return Context("tpu", device_id)


def cpu_pinned(device_id=0):
    return Context("cpu_pinned", device_id)


def current_context():
    """The innermost ``with ctx:`` context, else cpu/tpu default.

    Parity: ``mx.context.current_context`` — default is cpu() like the
    reference; accelerator placement is explicit (or via ``with mx.tpu():``).
    """
    stack = getattr(_tls, "stack", None)
    if stack:
        return stack[-1]
    return Context("cpu", 0)


def current_device():
    return current_context()


def num_gpus():
    """Number of accelerator devices visible (parity: ``mx.context.num_gpus``)."""
    return len(_accelerator_devices())


def num_tpus():
    return len(_accelerator_devices())
