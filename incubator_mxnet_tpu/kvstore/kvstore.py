"""KVStore facade — gradient aggregation / parameter synchronization.

Parity target: [U:src/kvstore/] + [U:python/mxnet/kvstore/kvstore.py].
The reference's machinery (CPU/GPU tree reduce for 'local'/'device'
[U:src/kvstore/comm.h], NCCL allreduce [U:src/kvstore/kvstore_nccl.h],
ps-lite parameter servers for 'dist_*' [U:src/kvstore/kvstore_dist.cc])
collapses onto XLA collectives:

* 'local'/'device'/'nccl' — in-process aggregation.  With one SPMD replica
  per process the sum over device replicas has already happened inside the
  compiled step (psum over the mesh), so push/pull degenerate to a
  key->value store with list-sum on push — semantically identical to the
  reference for the single-worker case and for Module's executor groups.
* 'dist_sync'/'dist_sync_device' — multi-process aggregation over
  jax.distributed (ICI/DCN collectives).  The PS tier (scheduler +
  servers + DMLC_* bootstrap) has no equivalent process: workers are SPMD
  peers.  ``set_optimizer`` therefore runs the optimizer locally on
  identically-replicated state — same result as server-side updates, no
  server.
* 'dist_async' — a REAL async tier (since round 5): a threaded TCP
  parameter server inside worker 0's process (``async_ps.py``), applying
  each worker's push the moment it arrives with the optimizer running
  server-side — the reference's ps-lite async contract, stragglers and
  all.  Optional SSP bound via MXNET_KVSTORE_MAX_STALENESS.  Elastic and
  fault-tolerant (this PR): heartbeat leases with eviction, idempotent
  retry over a per-client dedup window, server snapshot/restore, and a
  deterministic fault-injection harness (docs/fault_tolerance.md).
* gradient compression — per-worker gradients are quantized to 2-bit
  {-t, 0, +t} codes with an error-feedback residual *before* the wire
  (matching [U:src/kvstore/gradient_compression.cc]'s worker-side
  compress → push order); the cross-worker reduction then sums int8 codes
  (4× the wire bytes of fp32) and the aggregate is reconstructed as
  ``sum(codes) · t``.  The cross-worker sum accumulates in int32
  (jnp.sum's integer promotion), so code sums are exact at ANY worker
  count; int8 is the per-worker buffer/staging format (4× smaller than
  fp32 gradients), and the collective itself moves the promoted values.
  Since ISSUE 14 the codec tier (``comm/``: bf16 truncation, block-wise
  int8 with per-block scales) rides the same worker-side-compress
  contract: ``set_gradient_compression({"type": "int8"|"bf16"})`` for
  per-key pushes, and the ``MXNET_GRAD_COMPRESS`` policy for
  ``bucketed_pushpull``'s flat buckets (codec id namespacing the bucket
  keys beside the membership epoch) — docs/gradient_compression.md.
"""
from __future__ import annotations

import os as _os
from time import perf_counter as _perf

import numpy as _np

from .. import profiler as _profiler
from ..ndarray.ndarray import NDArray, array, zeros

__all__ = ["KVStore", "KVStoreLocal", "KVStoreDist", "KVStoreDistAsync",
           "bucket_bytes", "bucketed_pushpull", "plan_buckets",
           "execute_bucket", "retain_feedback", "create"]


# -- bucketed gradient allreduce --------------------------------------------
# MLPerf-scale TPU training aggregates gradients in size-capped flat buckets
# (arxiv 1909.09756); ps-lite sharded big tensors for the same reason.  The
# Trainer flattens same-dtype gradients into a few capped buffers and the
# dist store sees ONE pushpull per bucket instead of one per parameter.

def bucket_bytes():
    """Per-bucket byte cap for bucketed gradient allreduce
    (``MXNET_KVSTORE_BUCKET_BYTES``, default 4 MiB; 0 disables bucketing)."""
    try:
        return int(_os.environ.get("MXNET_KVSTORE_BUCKET_BYTES", str(4 << 20)))
    except ValueError:
        return 4 << 20


_UNFLATTEN_CACHE = {}


def _unflatten(flat, shapes):
    """Scatter a reduced flat bucket back into per-grad arrays — ONE jitted
    dispatch per bucket signature (static offsets), not a slice per param."""
    import jax

    key = (tuple(shapes), str(flat.dtype))
    fn = _UNFLATTEN_CACHE.get(key)
    fresh = fn is None
    if fresh:
        spans, off = [], 0
        for s in shapes:
            n = 1
            for d in s:
                n *= d
            spans.append((off, n, s))
            off += n

        def split(buf):
            return [buf[o:o + n].reshape(s) for o, n, s in spans]

        fn = _UNFLATTEN_CACHE[key] = jax.jit(split)
    tc = _perf() if fresh else None
    out = fn(flat)
    if tc is not None:
        _profiler.record_compile("kvstore.unflatten", {
            "__program__": "unflatten",
            "flat": _profiler.sig_array(flat),
            "layout": _profiler.sig_static(list(shapes)),
        }, (_perf() - tc) * 1e3)
    return out


_FLATTEN_JIT = None


def _flatten(raws):
    # one persistent jitted gather: jit's own aval cache keys the per-bucket
    # signatures (a fresh jit wrapper per call would recompile every step);
    # the profiler.jit_cache_size delta around the call is the exact O(1)
    # did-this-compile probe feeding the compile registry
    global _FLATTEN_JIT
    if _FLATTEN_JIT is None:
        import jax
        import jax.numpy as jnp

        _FLATTEN_JIT = jax.jit(
            lambda xs: jnp.concatenate([x.reshape(-1) for x in xs]))
    n0 = _profiler.jit_cache_size(_FLATTEN_JIT)
    tc = _perf()
    out = _FLATTEN_JIT(list(raws))
    if n0 >= 0 and _profiler.jit_cache_size(_FLATTEN_JIT) > n0:
        sig = {"__program__": "flatten"}
        for i, r in enumerate(raws):
            sig[f"x{i}"] = _profiler.sig_array(r)
        _profiler.record_compile("kvstore.flatten", sig, (_perf() - tc) * 1e3)
    return out


def plan_buckets(items, names=None, cap_bytes=None, compression=None,
                 epoch=0):
    """THE deterministic bucket-assignment rule (input order, split per
    (dtype, context, codec), size-capped), shared by
    :func:`bucketed_pushpull` and the Trainer's grad-readiness overlap
    hook (``Trainer.backward`` — docs/step_fold.md): both must format
    IDENTICAL buckets or peers' collectives would split.

    Returns ``(policy, buckets)`` where each bucket is a dict holding the
    wire key, the codec (or None for exact fp32), the positions of its
    member ``items``, and the raw fp32 byte count.  Only METADATA is read
    (dtype/shape/context) — gradient values may still be pending, so the
    plan can be drawn up before backward runs."""
    import numpy as np

    from ..comm import compression as _comp

    cap = bucket_bytes() if cap_bytes is None else cap_bytes
    policy = _comp.resolve_policy(compression)
    by_group = {}   # (dtype, ctx, codec_id) -> [(position, codec)]
    codecs = {"fp32": None}
    for i, (key, g) in enumerate(items):
        codec = None
        if policy is not None and str(g.dtype) == "float32":
            codec = policy.codec_for(names[i] if names is not None else None)
        cid = codec.id if codec is not None else "fp32"
        codecs.setdefault(cid, codec)
        # group by (dtype, context, codec): a flat bucket lives on ONE
        # device under ONE wire format, and the scattered pieces are
        # written back without a placement probe
        by_group.setdefault((str(g.dtype), str(g.context), cid),
                            []).append(i)
    buckets = []
    bucket_id = 0
    for (dt, _ctx, cid), members in by_group.items():
        itemsize = np.dtype(dt).itemsize
        start = 0
        while start < len(members):
            end, nbytes = start, 0
            while end < len(members):
                sz = items[members[end]][1].size * itemsize
                if end > start and nbytes + sz > cap:
                    break
                nbytes += sz
                end += 1
            # membership epoch + codec id namespace the bucket keys: any
            # store-side state hung off a key (compression residuals) must
            # not survive a worker-set change, and a worker toggling
            # MXNET_GRAD_COMPRESS mid-run renames its buckets so the
            # wire-agreement check fails loudly instead of peers decoding
            # each other's garbage
            buckets.append({
                "key": f"__grad_bucket__:{epoch}:{cid}:{dt}:{bucket_id}",
                "codec": codecs[cid],
                "cid": cid,
                "positions": tuple(members[start:end]),
                "nbytes": nbytes,
            })
            bucket_id += 1
            start = end
    return policy, buckets


def execute_bucket(kv, bucket, items, policy, feedback):
    """Allreduce ONE planned bucket through ``kv`` and scatter the reduced
    values back into its members' grad buffers in place.  The per-bucket
    wire: agreement check, jitted flatten, plain pushpull or the codec
    exchange (docs/gradient_compression.md), jitted scatter, counters +
    span.  Raises loudly — never scatters — when the wire fails (including
    the ``kvstore.bucket_drop_reply`` fault point of the chaos tier)."""
    from ..engine import DeferredArray
    from ..comm import compression as _comp
    from ..parallel import elastic as _elastic
    from ..utils import faultinject

    t0 = _perf() if _profiler._active else None
    chunk = [items[i] for i in bucket["positions"]]
    grads = [g for _, g in chunk]
    raws = []
    for g in grads:
        raw = g._data
        if isinstance(raw, DeferredArray):  # pending bulk op: flush first
            raw = raw._resolve()
            g._data = raw
        raws.append(raw)
    codec = bucket["codec"]
    bkey = bucket["key"]
    nbytes = bucket["nbytes"]
    use_ef = (feedback is not None and policy is not None
              and policy.error_feedback and codec is not None)
    # EVERY bucket enters the agreement check, fp32 ones included: the
    # asymmetric toggle (one worker compressed, a peer off) is exactly the
    # case where the off worker would otherwise issue a plain fp32
    # pushpull against the peer's scale/code collectives and deadlock
    # instead of failing loudly
    # a dead peer hangs the exchange forever — the collective watchdog
    # (parallel/elastic.py) bounds every bucket dispatch
    _elastic.watchdog_arm("kvstore.bucket")
    try:
        if hasattr(kv, "check_wire_agreement"):
            kv.check_wire_agreement(bkey)
        if codec is None:
            flat = NDArray(_flatten(raws), ctx=grads[0].context)
            kv.pushpull(bkey, flat, out=flat)
            reduced, wire_bytes, codec_s = flat._data, nbytes, 0.0
        else:
            flat = _flatten(raws)
            if use_ef:
                flat = feedback.compensate(bkey, flat)
            reduced, resid, wire_bytes, codec_s = _comp.bucket_allreduce(
                codec, flat, kv.wire_allreduce)
            if use_ef:
                feedback.update(bkey, resid)
    finally:
        _elastic.watchdog_disarm()
    if faultinject.active() and faultinject.fire("kvstore.bucket_drop_reply"):
        # chaos tier: the reduced payload never arrives.  Raise BEFORE the
        # scatter so the member grads keep their pre-exchange values — a
        # dropped reply must error loudly, never half-write a bucket.
        raise faultinject.FaultInjected(
            f"injected fault: reply for gradient bucket {bkey!r} dropped")
    pieces = _unflatten(reduced, [r.shape for r in raws])
    for g, piece in zip(grads, pieces):
        g._data = piece
        g._version += 1
    _profiler.incr("allreduce_bucket")
    _profiler.incr("allreduce_bucket_params", len(chunk))
    _comp.account(nbytes, wire_bytes, codec_s)
    if t0 is not None:
        # the nested kvstore.pushpull span carries the wire time; this one
        # adds flatten/codec/scatter overhead + the raw vs encoded payload
        # sizes (tools/trace_report.py comms)
        _profiler.record_span("kvstore.bucketed_pushpull", "comms",
                              t0, args={"params": len(chunk),
                                        "bytes": nbytes,
                                        "bytes_raw": nbytes,
                                        "bytes_wire": wire_bytes,
                                        "codec": bucket["cid"]})


def retain_feedback(policy, feedback, epoch):
    """Drop error-feedback residuals from other epochs/codecs — they
    describe a wire format that no longer exists.  Must run once per step
    BEFORE the first bucket executes (both entry points call it)."""
    if feedback is not None and policy is not None and policy.error_feedback:
        feedback.retain(f"__grad_bucket__:{epoch}:{policy.id}:")


def bucketed_pushpull(kv, items, cap_bytes=None, names=None,
                      compression=None, feedback=None):
    """Allreduce ``items`` (list of ``(key, grad_nd)``) through ``kv`` as
    size-capped flattened buckets, writing the reduced values back into each
    grad buffer in place.  Bucket assignment is deterministic (input order,
    split per dtype and per codec), so bucket keys — and any compression
    residual state hung off them — are stable across steps.

    Gradient compression (docs/gradient_compression.md): ``compression``
    resolves through ``comm.resolve_policy`` (None → the
    ``MXNET_GRAD_COMPRESS`` env tier).  Under an active policy, fp32
    grads whose parameter ``names`` entry is not opted out travel as
    encoded payloads — codec id + scales in the wire envelope, bucket
    keys namespaced by codec id beside the membership epoch — while
    opted-out groups keep their own fp32 buckets and stay bit-exact.
    ``feedback`` (a ``comm.ErrorFeedback``) carries per-bucket residuals
    across steps when the policy enables error feedback."""
    epoch = kv.membership_epoch() if hasattr(kv, "membership_epoch") else 0
    policy, buckets = plan_buckets(items, names=names, cap_bytes=cap_bytes,
                                   compression=compression, epoch=epoch)
    retain_feedback(policy, feedback, epoch)
    for bucket in buckets:
        execute_bucket(kv, bucket, items, policy, feedback)


def create(name="local"):
    """Parity: ``mx.kv.create``."""
    name = name.lower()
    if name in ("local", "local_allreduce_cpu", "local_allreduce_device", "device", "nccl"):
        return KVStoreLocal(name)
    if name == "dist_async":
        return KVStoreDistAsync(name)
    if name in ("dist_sync", "dist_sync_device", "dist_device_sync", "dist"):
        return KVStoreDist(name)
    if name in ("horovod", "byteps"):
        # plugin backends in the reference; SPMD collectives already provide
        # the allreduce path, so alias to dist.
        return KVStoreDist("dist_sync")
    raise ValueError(f"unknown kvstore type {name!r}")


class KVStore:
    """Base key-value store interface (parity: ``mx.kvstore.KVStore``)."""

    def __init__(self, name):
        self._type = name
        self._store = {}
        self._updater = None
        self._optimizer = None
        self._compression = None

    @property
    def type(self):
        return self._type

    @property
    def rank(self):
        return 0

    @property
    def num_workers(self):
        return 1

    # -- core ops --------------------------------------------------------
    def init(self, key, value):
        if isinstance(key, (list, tuple)):
            for k, v in zip(key, value):
                self.init(k, v)
            return
        if isinstance(value, (list, tuple)):
            value = value[0]
        self._store[key] = value.copy()

    def push(self, key, value, priority=0):
        if isinstance(key, (list, tuple)):
            for k, v in zip(key, value):
                self.push(k, v, priority)
            return
        t0 = _perf() if _profiler._active else None
        agg = self._aggregate(value)
        if self._compression is not None:
            # compress BEFORE the wire — the whole point of gradient
            # compression is what crosses the process boundary
            agg = self._compressed_reduce(key, agg)
        else:
            agg = self._reduce_across_workers(agg)
        if self._updater is not None:
            self._updater(key, agg, self._store[key])
        else:
            self._store[key] = agg
        if t0 is not None:
            _profiler.record_span("kvstore.push", "comms", t0)

    def pull(self, key, out=None, priority=0, ignore_sparse=True):
        if isinstance(key, (list, tuple)):
            for k, o in zip(key, out):
                self.pull(k, o, priority)
            return
        t0 = _perf() if _profiler._active else None
        value = self._store[key]
        outs = out if isinstance(out, (list, tuple)) else [out]
        for o in outs:
            value.copyto(o)
        if t0 is not None:
            _profiler.record_span("kvstore.pull", "comms", t0)

    def pushpull(self, key, value, out=None, priority=0):
        """Fused push+pull (parity: the 1.7 ``pushpull`` fast path /
        allreduce backends)."""
        if isinstance(key, (list, tuple)):
            for i, k in enumerate(key):
                self.pushpull(k, value[i], out[i] if out is not None else None, priority)
            return
        t0 = _perf() if _profiler._active else None
        agg = self._aggregate(value)
        if self._compression is not None:
            agg = self._compressed_reduce(key, agg)
        else:
            agg = self._reduce_across_workers(agg)
        if self._updater is not None:
            if key not in self._store:
                self.init(key, agg)
            self._updater(key, agg, self._store[key])
            result = self._store[key]
        else:
            result = agg
            self._store[key] = agg
        if out is not None:
            outs = out if isinstance(out, (list, tuple)) else [out]
            for o in outs:
                result.copyto(o)
        if t0 is not None:
            _profiler.record_span("kvstore.pushpull", "comms", t0)

    def broadcast(self, key, value, out=None, priority=0):
        self.init(key, value)
        if out is not None:
            self.pull(key, out, priority)

    def row_sparse_pull(self, key, out=None, priority=0, row_ids=None):
        # dense-on-TPU: equivalent to pull (documented divergence)
        self.pull(key, out, priority)

    def supports_grad_bucketing(self):
        """Whether ``bucketed_pushpull`` is sound against this store: only a
        pure allreduce tier qualifies — a store applying a per-key optimizer
        (updater/server-side optimizer) or per-key compression residual
        semantics must keep one key per parameter.  Local stores skip
        bucketing too: in-process pushpull is already free of wire cost."""
        return False

    def membership_epoch(self):
        """Monotonic epoch of the contributing worker set.  Static stores
        (local / SPMD dist, where membership is fixed at bootstrap) stay at
        0; the elastic async tier bumps it on join/leave/eviction so
        membership-derived state (bucket keys, compression residuals) is
        re-derived instead of carried across a membership change.

        Contract for a future store that is BOTH elastic and bucketing
        (none exists today — the async tier never buckets): the epoch fed
        into bucket keys must be step-synchronized across workers (e.g.
        agreed at a barrier), not read through a per-worker TTL cache —
        peers formatting the same step's buckets with different epochs
        would silently split the reduction."""
        return 0

    # -- helpers ---------------------------------------------------------
    def _aggregate(self, value):
        if isinstance(value, (list, tuple)):
            acc = value[0].copy()
            for v in value[1:]:
                acc += v
            return acc
        return value

    def _reduce_across_workers(self, value):
        return value

    def _reduce_codes(self, codes):
        """Cross-worker sum of int8 quantization codes (the wire format).
        Single-process base: identity.  Returns an int array."""
        return codes

    def wire_allreduce(self, arr, op="sum"):
        """Cross-worker reduce of a raw (possibly encoded) array — the
        transport compressed payloads ride (``comm.bucket_allreduce``).
        Single-process base: identity."""
        return arr

    def _quantize_2bit(self, key, grad):
        """Worker-side 2-bit quantization with error-feedback residual
        (parity: [U:src/kvstore/gradient_compression.cc]); returns the int8
        sign codes and the threshold — the wire format."""
        import jax.numpy as jnp

        threshold = float(self._compression.get("threshold", 0.5))
        res_key = ("__residual__", key)
        residual = self._store.get(res_key)
        if residual is None:
            residual = zeros(grad.shape, dtype=grad.dtype, ctx=grad.context)
        g = grad._data + residual._data
        codes = (jnp.where(g > threshold, 1, 0)
                 + jnp.where(g < -threshold, -1, 0)).astype(jnp.int8)
        residual._data = g - codes.astype(g.dtype) * threshold
        residual._version += 1
        self._store[res_key] = residual
        self._last_wire_dtype = str(codes.dtype)  # test/observability hook
        return codes, threshold

    def _compressed_reduce(self, key, grad):
        """Gradient compression applied worker-side BEFORE the cross-worker
        reduction (parity: [U:src/kvstore/kvstore_dist.cc] compresses, then
        ZPushes).  '2bit' (the reference scheme): int8 sign codes, aggregate
        ``sum(codes) · t``.  'bf16'/'int8' (the comm/ codec tier): jitted
        block-wise encode with per-key error feedback, reduced over
        ``wire_allreduce`` — scales max-reduce first so the integer code
        sum is exact at any worker count."""
        ctype = self._compression.get("type", "2bit")
        if ctype == "2bit":
            codes, threshold = self._quantize_2bit(key, grad)
            wire = self._reduce_codes(codes)
            return NDArray(wire.astype(grad._data.dtype) * threshold,
                           ctx=grad.context)
        from ..comm import compression as _comp

        codec = _comp.codec_from_params(self._compression)
        flat = grad._data.reshape(-1)
        use_ef = bool(self._compression.get(
            "error_feedback", codec.error_feedback_default))
        res_key = ("__residual__", key)
        residual = self._store.get(res_key) if use_ef else None
        reduced, resid, wire, codec_s = _comp.bucket_allreduce(
            codec, flat, self.wire_allreduce,
            residual=residual._data if residual is not None else None)
        if use_ef:
            self._store[res_key] = NDArray(resid, ctx=grad.context)
        self._last_wire_dtype = ("bfloat16" if isinstance(codec, _comp.Bf16Codec)
                                 else "int8")
        _comp.account(int(flat.nbytes), wire, codec_s)
        return NDArray(reduced.reshape(grad.shape).astype(grad._data.dtype),
                       ctx=grad.context)

    # -- optimizer plumbing ---------------------------------------------
    def set_optimizer(self, optimizer):
        """Parity: run the optimizer 'on the kvstore'.  No server tier: the
        updater runs locally on replicated state (same math, no RPC)."""
        from ..optimizer import get_updater

        self._optimizer = optimizer
        self._updater = get_updater(optimizer)

    def _set_updater(self, updater):
        self._updater = updater

    def set_gradient_compression(self, compression_params):
        self._compression = dict(compression_params)

    # -- persistence / barrier -------------------------------------------
    def save_optimizer_states(self, fname, dump_optimizer=False):
        if self._updater is None:
            raise ValueError("Cannot save states for distributed training without initializing the optimizer")
        with open(fname, "wb") as f:
            f.write(self._updater.get_states(dump_optimizer))

    def load_optimizer_states(self, fname):
        if self._updater is None:
            raise ValueError("Cannot load states without an optimizer set")
        with open(fname, "rb") as f:
            self._updater.set_states(f.read())

    def barrier(self):
        pass

    def _send_command_to_servers(self, head, body):
        pass  # no server tier


class KVStoreLocal(KVStore):
    """'local'/'device'/'nccl': single-process aggregation."""


class KVStoreDist(KVStore):
    """'dist_*': multi-process SPMD aggregation over jax.distributed.

    Process bootstrap honors the reference launcher's DMLC_* environment
    (set by ``tools/launch_local.py``, the [U:tools/launch.py] local-mode
    analog): DMLC_PS_ROOT_URI/DMLC_PS_ROOT_PORT = the jax.distributed
    coordinator, DMLC_NUM_WORKER = process count, DMLC_WORKER_ID = this
    process's id.  The scheduler/server roles have no process here — the
    coordinator thread inside worker 0 plays the scheduler, and there is
    no server tier (SPMD peers).
    """

    def __init__(self, name):
        super().__init__(name)
        self._initialized_dist = False
        self._mesh_cache = None
        self._reduce_fn_cache = {}    # op -> jitted stacked reducer
        self._ensure_dist()

    def supports_grad_bucketing(self):
        return (self._updater is None and self._optimizer is None
                and self._compression is None)

    def _ensure_dist(self):
        if self._initialized_dist:
            return
        n = int(_os.environ.get("DMLC_NUM_WORKER", "1"))
        if n > 1:
            # must run before anything touches the XLA backend — even
            # jax.process_count() would initialize it single-process
            import jax

            from ..parallel.mesh import init_distributed

            if not jax.distributed.is_initialized():
                init_distributed()
        self._initialized_dist = True

    @property
    def rank(self):
        import jax

        return jax.process_index()

    @property
    def num_workers(self):
        import jax

        return jax.process_count()

    # -- device-side collectives ----------------------------------------
    def _worker_mesh(self):
        """One device per process, mesh axis 'w' — the wire the reference's
        ps-lite ZMQ transport maps onto (XLA collectives over ICI/DCN).
        Memoized: Mesh identity keys the jit cache."""
        if self._mesh_cache is None:
            import jax
            from jax.sharding import Mesh

            first = {}
            for d in jax.devices():
                first.setdefault(d.process_index, d)
            devs = [first[i] for i in range(jax.process_count())]
            self._mesh_cache = Mesh(_np.array(devs), ("w",))
        return self._mesh_cache

    def _allreduce(self, arr, op="sum"):
        """Reduce ``arr`` (host or device value, identical shape on every
        worker) across processes with an on-device collective — no
        O(workers) host-side gather, and no D2H round-trip for
        device-resident gradients.  One jitted reducer per ``op``
        ('sum'/'max'/'min'); jit's own shape-keyed cache handles per-key
        shapes.  Integer sums promote (int8 codes accumulate in int32),
        so quantization-code sums are exact at any worker count."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P

        mesh = self._worker_mesh()
        fn = self._reduce_fn_cache.get(op)
        if fn is None:
            red = {"sum": jnp.sum, "max": jnp.max, "min": jnp.min}[op]
            fn = self._reduce_fn_cache[op] = jax.jit(
                lambda x, _red=red: _red(x, axis=0),
                out_shardings=NamedSharding(mesh, P()),
            )
        my_dev = mesh.devices.flat[
            [d.process_index for d in mesh.devices.flat].index(
                jax.process_index())]
        sharding = NamedSharding(mesh, P("w"))
        local = jax.device_put(jnp.expand_dims(jnp.asarray(arr), 0), my_dev)
        garr = jax.make_array_from_single_device_arrays(
            (jax.process_count(),) + tuple(local.shape[1:]), sharding, [local])
        out = fn(garr)
        return out.addressable_data(0)

    def wire_allreduce(self, arr, op="sum"):
        import jax

        if jax.process_count() == 1:
            return arr
        return self._allreduce(arr, op)

    def check_wire_agreement(self, key):
        """Fail LOUDLY if any peer formats this bucket differently.  The
        bucket key bakes in membership epoch, codec id, and dtype, so
        one cheap hash-allreduce catches a worker toggling
        ``MXNET_GRAD_COMPRESS`` (or its block size) mid-run — the
        alternative is feeding int8 codes into peers' fp32 sum and
        silently decoding garbage.  ``bucketed_pushpull`` runs this for
        EVERY bucket, uncompressed fp32 ones too, on every step (no
        per-key cache: a cached verdict would let the NON-toggling peer
        skip the check and issue its full-bucket collective against the
        toggler's hash check — exactly the mismatched-program hang this
        exists to prevent); the check is therefore the first collective
        each worker issues per bucket and an asymmetric toggle raises
        on both sides.  Cost: one (2,)-int32 allreduce per bucket,
        noise next to the payload collective it fronts."""
        import jax

        if jax.process_count() == 1:
            return
        import zlib

        h = zlib.crc32(key.encode()) & 0x3FFFFFFF
        # one collective: max over (h, -h) yields (max_h, -min_h)
        pair = self._allreduce(_np.asarray([h, -h], _np.int32), "max")
        hi, neg_lo = (int(x) for x in _np.asarray(pair))
        if hi != h or -neg_lo != h:
            raise RuntimeError(
                f"gradient-bucket wire-format mismatch: this worker "
                f"formats {key!r} but a peer disagrees — compression "
                "codec, block size, or membership epoch toggled mid-run? "
                "All workers must run the same MXNET_GRAD_COMPRESS "
                "configuration.")

    def _reduce_across_workers(self, value):
        import jax

        if jax.process_count() == 1:
            return value
        return NDArray(self._allreduce(value._data), ctx=value.context)

    def _reduce_codes(self, codes):
        import jax

        if jax.process_count() == 1:
            return codes
        return self._allreduce(codes)

    def barrier(self):
        import jax

        if jax.process_count() > 1:
            from jax.experimental import multihost_utils

            multihost_utils.sync_global_devices("kvstore_barrier")


class KVStoreDistAsync(KVStore):
    """'dist_async': barrier-free push/pull against the TCP parameter
    server in worker 0 (see ``async_ps.py``).  Pure control-plane sockets —
    no jax.distributed, no collectives, hence no implicit barriers: a
    straggler cannot block its peers (parity:
    [U:src/kvstore/kvstore_dist.cc] async mode).

    Elastic + fault-tolerant (docs/fault_tolerance.md): the store registers
    its rank on construction and renews the lease from a background
    heartbeat thread; requests retry with reconnect+replay against the
    server's dedup window; ``close()`` (or ``Trainer.close()``) leaves the
    membership immediately instead of waiting out the lease."""

    def __init__(self, name):
        super().__init__(name)
        from . import async_ps

        self._rank = int(_os.environ.get("DMLC_WORKER_ID", "0"))
        self._num_workers = int(_os.environ.get("DMLC_NUM_WORKER", "1"))
        host = _os.environ.get("DMLC_PS_ROOT_URI", "127.0.0.1")
        self._server = async_ps.serve_if_rank0(self._rank, self._num_workers)
        self._client = async_ps.AsyncClient(host, async_ps.server_port())
        lease_s = float(self._client.request("register", self._rank))
        # multi-rank trace alignment (ISSUE 7): pin this process's rank in
        # the profiler and take a one-shot midpoint-of-RTT clock-offset
        # sample against the server's wall clock (the heartbeat thread
        # keeps refreshing it for the life of the store)
        _profiler.set_process_info(rank=self._rank)
        try:
            _profiler.sample_clock_offset(
                lambda: self._client.request("clock"), samples=5)
        except Exception:
            pass  # pre-ISSUE-7 server: no clock on the wire
        self._heartbeat = async_ps.HeartbeatThread(
            host, async_ps.server_port(), self._rank,
            interval=max(0.05, lease_s / 3.0))
        self._heartbeat.start()
        self._members_cache = None   # (expires_at, {"epoch","ranks"})
        self._members_ttl = max(0.2, lease_s / 4.0)
        self._closed = False

    def supports_grad_bucketing(self):
        # never: the async server ACCUMULATES pushes to an existing key
        # (no per-step reset), so a reused bucket key would pull back the
        # running sum of every previous step's gradients.  The async
        # contract is a server-side optimizer per key, not an allreduce.
        return False

    @property
    def rank(self):
        return self._rank

    @property
    def num_workers(self):
        # the CONFIGURED cluster size (scaling denominators and launch
        # assertions key off this); live membership is num_live_workers()
        return self._num_workers

    # -- elastic membership ----------------------------------------------
    def _members(self):
        from time import monotonic as _mono

        if self._members_cache is not None and \
                self._members_cache[0] > _mono():
            return self._members_cache[1]
        val = self._client.request("members")
        self._members_cache = (_mono() + self._members_ttl, val)
        return val

    def live_workers(self):
        """Ranks currently holding (or grandfathered into) a live lease."""
        return list(self._members()["ranks"])

    def num_live_workers(self):
        return len(self.live_workers())

    def membership_epoch(self):
        return int(self._members()["epoch"])

    def close(self):
        """Leave the cluster cleanly: deregister (peers' barrier/SSP
        accounting shrinks NOW, no eviction window), stop heartbeating,
        drop the connection.  Idempotent."""
        if self._closed:
            return
        self._closed = True
        self._heartbeat.stop()
        try:
            self._client.request("deregister", self._rank)
        except Exception:
            pass  # server already gone: nothing to leave
        self._client.close()

    def init(self, key, value):
        if isinstance(key, (list, tuple)):
            for k, v in zip(key, value):
                self.init(k, v)
            return
        if isinstance(value, (list, tuple)):
            value = value[0]
        self._client.request("init", key, _np.asarray(value.asnumpy()))

    def push(self, key, value, priority=0):
        if isinstance(key, (list, tuple)):
            for k, v in zip(key, value):
                self.push(k, v, priority)
            return
        t0 = _perf() if _profiler._active else None
        agg = self._aggregate(value)
        if self._compression is None:
            self._client.request("push", key, _np.asarray(agg.asnumpy()),
                                 self._rank)
        elif self._compression.get("type", "2bit") == "2bit":
            # the int8 CODES cross the TCP wire (the whole point of
            # gradient compression is what crosses the process boundary);
            # the server decodes as codes · threshold before applying
            codes, threshold = self._quantize_2bit(key, agg)
            self._client.request("push_codes", key, _np.asarray(codes),
                                 threshold, self._rank)
        else:
            self._push_encoded(key, agg)
        if t0 is not None:
            _profiler.record_span("kvstore.push", "comms", t0)

    def _push_encoded(self, key, agg):
        """Codec-tier push (comm/): jitted encode with per-key error
        feedback worker-side, codec id + scales in the wire envelope; the
        server accumulates decoded fp32."""
        from ..comm import compression as _comp

        codec = _comp.codec_from_params(self._compression)
        t0 = _perf()
        flat = agg._data.reshape(-1)
        use_ef = bool(self._compression.get(
            "error_feedback", codec.error_feedback_default))
        res_key = ("__residual__", key)
        if use_ef:
            residual = self._store.get(res_key)
            if residual is not None:
                # same jitted add the bucket path compensates with
                flat = _comp._add_fn()(flat, residual._data)
        payload, resid = codec.encode(flat)
        if use_ef:
            self._store[res_key] = NDArray(resid, ctx=agg.context)
        np_payload = {k: _np.asarray(v) for k, v in payload.items()}
        codec_s = _perf() - t0
        wire = sum(int(a.nbytes) for a in np_payload.values())
        self._last_wire_dtype = str(
            np_payload.get("codes", np_payload.get(
                "enc", np_payload.get("packed"))).dtype)
        _comp.account(int(flat.nbytes), wire, codec_s)
        self._client.request("push_enc", key, codec.id, np_payload,
                             int(flat.size), list(agg.shape), self._rank)

    def pull(self, key, out=None, priority=0, ignore_sparse=True):
        if isinstance(key, (list, tuple)):
            for k, o in zip(key, out):
                self.pull(k, o, priority)
            return
        t0 = _perf() if _profiler._active else None
        if self._compression is not None and \
                self._compression.get("type", "2bit") != "2bit":
            value = self._pull_encoded(key)
        else:
            value = self._client.request("pull", key)
        outs = out if isinstance(out, (list, tuple)) else [out]
        for o in outs:
            array(value, ctx=o.context).copyto(o)
        if t0 is not None:
            _profiler.record_span("kvstore.pull", "comms", t0)

    def _pull_encoded(self, key):
        """Codec-tier pull (the ENCODED pull leg, push_enc's mirror): the
        versioned request names the bucket codec, the server encodes the
        aggregated fp32 value server-side, this client decodes.  No error
        feedback — pull is a read against the server's fp32 master, so
        the quantization error is per-read, never accumulated.  Envelope
        checks fail loudly (PSProtocolError): a silent fp32 fallback or a
        misdecoded payload would be invisible until convergence drifted."""
        from ..comm import compression as _comp
        from .async_ps import PSProtocolError

        codec = _comp.codec_from_params(self._compression)
        env = self._client.request("pull_enc", key, codec.id,
                                   _comp.PULL_ENC_WIRE_VERSION)
        if not isinstance(env, dict) or \
                env.get("v") != _comp.PULL_ENC_WIRE_VERSION:
            raise PSProtocolError(
                f"pull_enc reply for {key!r} is not a "
                f"v{_comp.PULL_ENC_WIRE_VERSION} envelope (got "
                f"{type(env).__name__}): mixed old-server/new-client "
                "deployment — upgrade the server")
        if env.get("codec") != codec.id:
            raise PSProtocolError(
                f"pull_enc codec-id mismatch for {key!r}: asked "
                f"{codec.id!r}, server answered {env.get('codec')!r}")
        t0 = _perf()
        flat = _comp.decode_np(codec.id, env["payload"], int(env["n"]))
        codec_s = _perf() - t0
        wire = sum(int(_np.asarray(a).nbytes)
                   for a in env["payload"].values())
        self._last_wire_dtype = str(
            env["payload"].get(
                "codes", env["payload"].get(
                    "enc", env["payload"].get("packed"))).dtype)
        _comp.account(4 * int(env["n"]), wire, codec_s)
        return flat.reshape(env["shape"])

    def pushpull(self, key, value, out=None, priority=0):
        self.push(key, value, priority)
        if out is not None:
            self.pull(key, out, priority)

    def set_optimizer(self, optimizer):
        """Ship the optimizer to the SERVER (the reference sends it to the
        ps-lite servers the same way); pushes then apply updates there."""
        import pickle as _pickle

        self._optimizer = optimizer
        if self._rank == 0:
            self._client.request("set_optimizer", _pickle.dumps(optimizer))
        self.barrier()  # all workers see server-side updates from here on

    def push_counts(self):
        """Per-worker applied-push counts (observability / SSP tests)."""
        return self._client.request("counts")

    def cluster_metrics(self):
        """The server's per-rank metrics snapshots (heartbeat piggyback):
        ``{rank: snapshot}`` — what rank 0's /metrics scrape aggregates."""
        return self._client.request("metrics")

    def barrier(self):
        self._client.request("barrier")


_np  # keep import
array  # re-export convenience
