"""Device-mesh construction and multi-host bootstrap.

Replaces the reference's cluster topology machinery: ps-lite's
scheduler/server/worker roles wired by ``DMLC_*`` env vars
([U:3rdparty/ps-lite/], [U:tools/launch.py]) collapse onto
``jax.distributed.initialize`` (coordination service) plus a named
``jax.sharding.Mesh`` over which every collective rides ICI (intra-slice)
or DCN (inter-slice).

Axis convention (the full modern menu — SURVEY.md §2.3):

====  =======================================================
dp    data parallel (batch split; grads psum'd by XLA)
fsdp  ZeRO-style parameter/optimizer-state sharding (dp-domain)
tp    tensor parallel (weight matrices split)
pp    pipeline parallel (layer stages)
sp    sequence/context parallel (ring attention)
ep    expert parallel (MoE experts)
====  =======================================================

Size-1 axes are kept in the mesh so PartitionSpecs mentioning them are
always valid; XLA treats size-1 axes as free.
"""
from __future__ import annotations

import contextlib
import dataclasses
import os
import threading

import numpy as _np

import jax

__all__ = [
    "MeshConfig",
    "make_mesh",
    "current_mesh",
    "local_mesh",
    "init_distributed",
    "mesh_scope",
    "sync_profiler_clock",
]


# Outermost → innermost.  jax.devices() enumerates in topology order on TPU
# and the last axes step fastest through it, so the bandwidth-hungriest
# axes (tp per-layer collectives, then sp ring traffic) sit innermost =
# ICI-adjacent; low-traffic axes (pp point-to-point, dp once-per-step psum)
# sit outermost.
AXES = ("dp", "pp", "fsdp", "ep", "sp", "tp")

_tls = threading.local()


@dataclasses.dataclass(frozen=True)
class MeshConfig:
    """Declarative mesh shape.  ``dp=None`` means "whatever is left over"
    after the explicit axes divide the device count."""

    dp: int | None = None
    fsdp: int = 1
    tp: int = 1
    pp: int = 1
    sp: int = 1
    ep: int = 1

    def resolve(self, n_devices: int) -> dict:
        fixed = self.fsdp * self.tp * self.pp * self.sp * self.ep
        dp = self.dp
        if dp is None:
            if n_devices % fixed != 0:
                raise ValueError(
                    f"device count {n_devices} not divisible by "
                    f"fsdp*tp*pp*sp*ep = {fixed}"
                )
            dp = n_devices // fixed
        if dp * fixed != n_devices:
            raise ValueError(
                f"mesh {dp}x{self.fsdp}x{self.tp}x{self.pp}x{self.sp}x{self.ep}"
                f" != device count {n_devices}"
            )
        return dict(dp=dp, fsdp=self.fsdp, tp=self.tp, pp=self.pp, sp=self.sp, ep=self.ep)


def make_mesh(config: MeshConfig | None = None, devices=None, **axis_sizes) -> jax.sharding.Mesh:
    """Build a named mesh.  ``make_mesh(tp=2)`` → dp fills the rest.

    Axis order/locality rationale: see the ``AXES`` comment above.
    """
    if config is None:
        config = MeshConfig(**axis_sizes)
    elif axis_sizes:
        raise ValueError("pass either a MeshConfig or axis sizes, not both")
    if devices is None:
        devices = jax.devices()
    sizes = config.resolve(len(devices))
    shape = tuple(sizes[a] for a in AXES)
    arr = _np.asarray(devices, dtype=object).reshape(shape)
    return jax.sharding.Mesh(arr, AXES)


def local_mesh() -> jax.sharding.Mesh:
    """Pure data-parallel mesh over all visible devices (the analog of the
    reference's default ``ctx=[gpu(i) for i in range(num_gpus())]``)."""
    return make_mesh(MeshConfig())


def current_mesh() -> jax.sharding.Mesh | None:
    return getattr(_tls, "mesh", None)


@contextlib.contextmanager
def mesh_scope(mesh: jax.sharding.Mesh):
    """Scope a default mesh for SPMDTrainer / sharded ops."""
    prev = getattr(_tls, "mesh", None)
    _tls.mesh = mesh
    try:
        yield mesh
    finally:
        _tls.mesh = prev


def init_distributed(coordinator_address=None, num_processes=None, process_id=None):
    """Multi-host bootstrap (the scheduler-role analog of ps-lite's
    ``DMLC_PS_ROOT_URI`` wiring, [U:3rdparty/ps-lite/src/van.cc]).

    Reads the reference-shaped env vars when args are omitted so launch
    scripts written for ``tools/launch.py`` conventions keep working:
    ``DMLC_PS_ROOT_URI:DMLC_PS_ROOT_PORT`` → coordinator,
    ``DMLC_NUM_WORKER`` → num_processes, ``DMLC_WORKER_ID`` → process_id.
    """
    if coordinator_address is None:
        uri = os.environ.get("DMLC_PS_ROOT_URI")
        port = os.environ.get("DMLC_PS_ROOT_PORT", "9000")
        if uri:
            coordinator_address = f"{uri}:{port}"
    if num_processes is None:
        nw = os.environ.get("DMLC_NUM_WORKER")
        num_processes = int(nw) if nw else None
    if process_id is None:
        wid = os.environ.get("DMLC_WORKER_ID")
        process_id = int(wid) if wid else None
    if coordinator_address is None:
        return  # single-process
    # The CPU backend ships no cross-process collectives by default
    # ("Multiprocess computations aren't implemented on the CPU backend");
    # multi-process CPU runs (the dist test tier, local launch) need the
    # gloo implementation selected BEFORE the backend initializes.
    if "cpu" in os.environ.get("JAX_PLATFORMS", "").lower():
        impl = os.environ.get("JAX_CPU_COLLECTIVES_IMPLEMENTATION", "gloo")
        jax.config.update("jax_cpu_collectives_implementation", impl)
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
    )
    if os.environ.get("MXNET_PROFILER_CLOCK_SYNC", "1") != "0":
        # one bootstrap-time collective right after the cluster-wide
        # rendezvous above: every process that reaches initialize() also
        # reaches this, so the broadcast cannot orphan a rank
        sync_profiler_clock()


# epoch the cross-host clock exchange is encoded against: unix seconds do
# not fit float32 (eps ~2 min at 1.7e9) and the test/CPU tiers run with
# x64 disabled, so the wire carries (int32 seconds since this base,
# int32 microseconds) instead of one float
_CLOCK_BASE_UNIX = 1_600_000_000


def sync_profiler_clock(samples=3):
    """One-shot clock-offset estimate for the SPMD ``dist_sync`` tier
    (the async tier samples against the PS heartbeat wire instead):
    broadcast process 0's wall clock over the mesh collectives and
    attribute it to the local send/receive midpoint, min-RTT sample wins
    (``profiler.update_clock_offset``).  Collective: EVERY process must
    call this the same number of times.  Never raises — observability
    must not take bootstrap down."""
    from .. import profiler

    try:
        if jax.process_count() <= 1:
            return None
        from jax.experimental import multihost_utils

        import time as _time

        profiler.set_process_info(rank=jax.process_index())

        def one_round():
            t0 = _time.time()
            now = _time.time()
            payload = _np.array(
                [int(now) - _CLOCK_BASE_UNIX, int((now % 1.0) * 1e6)],
                dtype=_np.int32)
            out = _np.asarray(multihost_utils.broadcast_one_to_all(payload))
            t1 = _time.time()
            ref = _CLOCK_BASE_UNIX + int(out[0]) + int(out[1]) / 1e6
            return ((t0 + t1) / 2.0 - ref, t1 - t0)

        # warmup round, DISCARDED: a barrier collective is not a request —
        # the broadcast value is process 0's clock at ITS entry, so a rank
        # arriving late sees a tiny t0..t1 window around an arbitrarily
        # stale reference (min-RTT would prefer exactly that sample).  The
        # warmup absorbs compile time and releases every rank from the
        # same instant; the sampled rounds that follow are entered nearly
        # simultaneously, so their midpoint error really is ~rtt-bounded.
        one_round()
        best = None
        for _ in range(max(1, int(samples))):
            off, rtt = one_round()
            if best is None or rtt < best[1]:
                best = (off, rtt)
        profiler.update_clock_offset(*best)
        return best
    except Exception:
        return None
