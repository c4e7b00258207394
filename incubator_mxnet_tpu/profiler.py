"""``mx.profiler`` — structured tracing / telemetry bridge.

Parity target: [U:python/mxnet/profiler.py] over the C++ engine profiler
([U:src/profiler/profiler.cc]).  The reference instruments every engine op
and dumps chrome://tracing JSON; this module restores that contract on top
of the jax_graft stack with three cooperating layers:

1. **Span recorder** — a per-thread ring buffer of ``(name, category,
   t0, duration, step, args)`` spans, armed by ``start()``.  Every hot
   path that already reports counters (dispatch cache, engine bulk flush,
   fused optimizer step, kvstore pushpull, io prefetch, trainer step
   boundaries) records spans into it; ``dump()`` serializes the rings to a
   chrome://tracing JSON at ``_config['filename']`` (paired B/E events,
   for runs without xprof).  A measured interval is a ``span``, which
   ALSO enters a ``jax.profiler.TraceAnnotation``: whoever started the
   JAX trace session, the program's spans sit in its ``.xplane.pb`` on
   the device's clock.  With both sinks off a span costs under a
   microsecond; post-hoc ``record_span`` sites are ring-only.

2. **xprof bridge** — ``start()``/``stop()`` still drive
   ``jax.profiler`` (XLA/xprof device traces, incl. per-HLO timing); a
   broken xprof install warns ONCE and bumps the ``profiler_trace_error``
   counter instead of failing silently.

3. **Per-step telemetry** — ``step_boundary()`` (called by
   ``gluon.Trainer.step``) closes a step: its wall time is split into
   host-dispatch / comms / device buckets from the spans recorded inside
   it, appended to a rolling window (``step_stats()``), checked by the
   slow-step detector (``MXNET_PROFILER_SLOW_STEP_MS`` or an automatic
   rolling-percentile mode — one breakdown log line per anomalous step),
   and device-memory watermarks are sampled via ``Device.memory_stats()``.

Since ISSUE 7 the profiler is **cluster-aware**:

* every trace carries process metadata (rank/host/pid) plus a wall-clock
  anchor and a midpoint-of-RTT **clock-offset estimate**
  (``update_clock_offset``; sampled against the async-PS wall clock or a
  one-shot ``parallel.mesh`` broadcast), so ``tools/trace_merge.py`` can
  fuse per-rank dumps into ONE offset-corrected Perfetto timeline;
* a **metrics registry** (``metrics_snapshot()``) periodically writes
  per-rank JSONL (``MXNET_METRICS_JSONL``) and serves Prometheus text
  from a stdlib-http endpoint (``MXNET_METRICS_PORT``, 0 = off); peers'
  snapshots arrive via ``publish_peer_metrics`` (the async-PS heartbeat
  wire feeds it), so one scrape of rank 0 sees the whole cluster;
* the slow-step detector compares per-rank step wall-times from those
  snapshots and names the slowest rank with its host/comms/device split
  (**straggler attribution** — ``straggler_report()``).

Since ISSUE 10 the profiler also owns **compilation observability**: a
process-wide compile registry every jit site reports into
(``record_compile``), per-recompile attribution naming the exact drifted
argument, XLA cost accounting, and a steady-state compile guard
(``MXNET_COMPILE_GUARD``) — see the Compilation observability section
below and ``tools/compile_report.py``.

Since ISSUE 12 it owns **device-memory observability** too: a live HBM
ledger every buffer-holding subsystem registers into (``track_memory``;
donation-aware, exact by construction), OOM forensics (the dispatch
choke points route ``RESOURCE_EXHAUSTED`` through
``maybe_oom_postmortem`` — one structured report naming the top owners
by bytes), a ``MemoryBudget`` admission API
(``MXNET_MEM_BUDGET_MB``), and a per-device memory counter track in the
chrome trace — see the Device-memory observability section below and
``tools/memory_report.py``.

Counters are **strict** since ISSUE 5: ``incr`` on an undeclared name
raises (a typo'd instrumentation site fails loudly instead of reporting
zeros forever); extensions register theirs via ``declare_counter()``.

``MXNET_PROFILER_AUTOSTART=1`` is honored at import like the reference
env var.  See docs/observability.md for the full tour.
"""
from __future__ import annotations

import atexit
import gzip as _gzip
import json
import logging
import os
from collections import OrderedDict as _OrderedDict
import socket as _socket
import threading as _threading
import time
import warnings as _warnings
import weakref as _weakref

import jax

__all__ = ["set_config", "start", "stop", "dump", "dumps", "pause", "resume",
           "scope", "span", "Marker", "state", "counters", "reset_counters",
           "incr", "incr_labeled", "counter_labels", "declare_counter",
           "record_span", "step_boundary",
           "current_step", "step_stats", "memory_watermark", "recorder_stats",
           "recording_enabled", "process_info", "set_process_info",
           "update_clock_offset", "sample_clock_offset", "metrics_snapshot",
           "publish_peer_metrics", "peer_metrics", "forget_peer_metrics",
           "register_metrics_provider", "unregister_metrics_provider",
           "render_prometheus",
           "start_metrics", "stop_metrics", "metrics_server_port",
           "straggler_report",
           # -- goodput ledger (ISSUE 20) --
           "goodput_snapshot", "cluster_goodput", "record_downtime",
           "reset_goodput",
           # -- compilation observability (ISSUE 10) --
           "record_compile", "compile_site", "compile_registry",
           "compiled_text",
           "compile_stats", "reset_compiles", "sig_array", "sig_static",
           "diff_signatures", "compile_cost_enabled", "jit_cache_size",
           "arm_compile_guard", "disarm_compile_guard", "compile_guard_state",
           "compile_guard_paused", "CompileGuardError",
           # -- device-memory observability (ISSUE 12) --
           "track_memory", "memory_ledger", "memory_postmortems",
           "array_nbytes", "device_memory_stats", "sample_device_memory",
           "maybe_sample_memory", "memory_budget", "MemoryBudget",
           "MemoryBudgetError", "oom_postmortem", "maybe_oom_postmortem",
           "is_resource_exhausted"]

_logger = logging.getLogger(__name__)

_config = {
    "filename": "profile.json",   # reference default profile_output.json-ish
    "profile_all": False,
    "profile_symbolic": True,
    "profile_imperative": True,
    "profile_memory": True,
    "profile_api": True,
    "aggregate_stats": False,
    # -- ISSUE 5 tracing/telemetry knobs --------------------------------
    "ring_size": int(os.environ.get("MXNET_PROFILER_RING_SIZE", "65536")),
    "slow_step_ms": None,          # explicit threshold; None = auto mode
    "slow_step_auto": True,        # rolling-percentile detector when no
    "slow_step_auto_mult": 4.0,    # explicit threshold is configured
    "step_window": 256,            # rolling step-stats window length
    "memory_sampling": True,       # Device.memory_stats() at step bounds
}
_state = {"running": False, "dir": None, "t0": None, "xprof": False}
_agg = {}  # name -> [count, total_s]; guarded by _counter_lock (scopes run
           # concurrently on the engine's per-thread bulk queues)

# perf_counter epoch all trace timestamps are relative to (chrome trace ts
# is in us; an absolute perf_counter would overflow viewer precision)
_EPOCH = time.perf_counter()
# wall-clock instant of _EPOCH (ts=0 of every trace this process dumps):
# the anchor tools/trace_merge.py aligns per-rank timelines with.  Sampled
# as the mean of two wall readings bracketing the perf reading so the
# pairing error is bounded by half the triple-read, not a full read.
_wt0 = time.time()
_EPOCH_UNIX = (_wt0 + time.time()) / 2.0 - (time.perf_counter() - _EPOCH)
del _wt0
_perf = time.perf_counter
_TraceAnnotation = jax.profiler.TraceAnnotation


def _env_float(name, default):
    try:
        return float(os.environ[name])
    except (KeyError, ValueError):
        return default


def _env_int(name, default):
    # one parse rule for env knobs across the repo (serving, io): a typo'd
    # value degrades to the default instead of raising
    try:
        return int(os.environ[name])
    except (KeyError, ValueError):
        return default


def _tally(name, dur):
    # under the counter lock: an unlocked read-modify-write on the shared
    # dict drops tallies across concurrent scopes and lets dumps() observe
    # a dict mutating mid-iteration
    with _counter_lock:
        cnt_tot = _agg.setdefault(name, [0, 0.0])
        cnt_tot[0] += 1
        cnt_tot[1] += dur


# -- dispatch/engine event counters -----------------------------------------
# The eager dispatch accelerator (ops/registry.py cache + engine.py bulking)
# and the fused trainer step (optimizer/fused.py + kvstore bucketing) report
# their behavior here so the wins are observable: cache hits/misses,
# raw-path bypasses, jit fallbacks, bulk flush sizes, fused-update group
# sizes, and allreduce bucket counts.  Plain int adds — cheap enough to
# stay on even when tracing is off.
#
# The dict below is THE declared set: ``incr`` on any other name raises
# (tools/lint_counters.py greps the tree against it), and extensions add
# theirs via ``declare_counter()``.

_counters = {
    "dispatch_cache_hit": 0,
    "dispatch_cache_miss": 0,
    "dispatch_cache_bypass": 0,
    "dispatch_cache_fallback": 0,
    "bulk_flush": 0,
    "bulk_ops_flushed": 0,
    "bulk_fallback": 0,
    "fused_step_call": 0,             # grouped optimizer dispatches
    "fused_step_params": 0,           # params updated through fused groups
    "fused_step_fallback_params": 0,  # params that took the per-tensor loop
    "step_fold_call": 0,              # folded-step single-program dispatches
    "step_fold_fallback": 0,          # fold entries that ran the eager path
                                      # (per-reason split: counter_labels())
    "fold_eval_call": 0,              # folded-eval single-program dispatches
    "allreduce_overlap_launched": 0,  # buckets pushed from the grad-readiness
                                      # hook DURING backward (overlap path)
    "allreduce_bucket": 0,            # bucketed gradient pushpulls
    "allreduce_bucket_params": 0,     # grads carried by those buckets
    "comms_bytes_raw": 0,             # gradient bytes before compression
    "comms_bytes_wire": 0,            # encoded gradient bytes on the wire
    "comms_compress_ms": 0,           # host-side codec encode/decode wall ms
    "comms_ring_hops": 0,             # encoded ppermute hops issued by the
                                      # quantized ring collectives (per step:
                                      # 2(D-1) per active ring stage)
    "profiler_trace_error": 0,        # jax.profiler start/stop failures
    "slow_step_detected": 0,          # slow-step detector firings
    "io_prefetch_batches": 0,         # batches produced by prefetch workers
    "io_pipeline_batches": 0,         # device-resident batches DataPipeline delivered
    "io_pipeline_stalls": 0,          # consumer arrivals that found the buffer empty
    "io_pipeline_wait_us": 0,         # whole microseconds those arrivals then waited
    "io_pipeline_depth_change": 0,    # autotuner depth raises + lowers
    "io_pipeline_bytes": 0,           # host->device bytes the transfer thread moved
    "ps_retry": 0,                    # async-PS client request retries
    "ps_reconnect": 0,                # async-PS client reconnects
    "ps_dedup_hit": 0,                # duplicate requests the PS suppressed
    "ps_eviction": 0,                 # workers evicted on lease expiry
    "ps_heartbeat_miss": 0,           # heartbeats that failed or arrived late
    "ps_snapshot": 0,                 # PS state snapshots written
    "fault_injected": 0,              # faultinject.py points that fired
    "metrics_snapshot": 0,            # metrics_snapshot() captures taken
    "metrics_scrape": 0,              # HTTP GETs served by the endpoint
    "straggler_detected": 0,          # cross-rank straggler attributions
    "serving_request": 0,             # requests accepted by InferenceServer
    "serving_batch": 0,               # dynamic batches dispatched
    "serving_batch_requests": 0,      # requests carried by those batches
    "serving_bucket_hit": 0,          # batches landing on a warm bucket
    "serving_bucket_miss": 0,         # batches that had to bind/compile
    "serving_slo_violation": 0,       # requests completing past their SLO
    "serving_queue_depth_peak": 0,    # high-watermark of the request queue
    "generation_request": 0,          # prompts accepted by GenerationServer
    "generation_shed": 0,             # submissions rejected by admission control
    "generation_prefill": 0,          # compiled prefill dispatches
    "generation_slot_join": 0,        # requests joining the decode batch
    "generation_slot_leave": 0,       # requests leaving (finish/cancel/error)
    "generation_decode_iter": 0,      # per-pool compiled decode steps
    "generation_token": 0,            # tokens emitted by decode steps
    "generation_cancelled": 0,        # requests cancelled mid-stream
    "generation_slo_violation": 0,    # completions past their tenant's SLO
    "pipeline_step": 0,               # scheduled pipeline steps dispatched
    "pipeline_microbatch": 0,         # microbatches retired by those steps
    "pipeline_bubble_ms": 0,          # modeled schedule bubble ms (rounded per step)
    "moe_tokens_dropped": 0,          # token-choice slots dropped at expert capacity
    "moe_rows_routed_here": 0,        # (token, choice) pairs routed to experts this chip holds
    "moe_step": 0,                    # compiled steps whose routing metrics were read
    "attention_dispatch_pallas": 0,   # attention call sites traced onto the Pallas kernels
    "attention_dispatch_xla": 0,      # attention call sites traced onto the XLA path
    "attention_dispatch_grouped": 0,  # of either: call sites with fewer key/value heads than query heads
    "ssm_scan_traced": 0,             # chunked state-space scan call sites traced into a program
    "ssm_scan_dispatch_pallas": 0,    # ... of them traced onto its Pallas kernels
    "ssm_scan_dispatch_xla": 0,       # ... of them traced onto the XLA chunks
    "kda_traced": 0,                  # chunked gated delta rule (KDA) call sites traced into a program
    "short_conv_traced": 0,           # gated short convolution call sites traced into a program
    "attention_dispatch_masked": 0,   # of the Pallas ones: call sites given a selection of keys a query
    "attention_dispatch_folded": 0,   # of the Pallas ones: causal call sites traced onto the folded kernels
    "sparse_attention_traced": 0,     # indexer-selected attention call sites traced into a program
    "sparse_attn_tiles_live": 0,      # 512 x 512 score tiles that hold a selected pair, over layers and steps
    "sparse_attn_tiles_causal": 0,    # ... and those at or below the diagonal
    "index_scores_dispatch_pallas": 0,  # index_scores call sites traced onto its Pallas kernels
    "index_scores_dispatch_xla": 0,   # index_scores call sites traced onto the XLA tiles
    "remat_kept_bytes": 0,            # bytes of named values the layer checkpoints of a traced step keep
    "moe_grouped_dispatch_pallas": 0,  # moe_ffn_dropless call sites traced onto the Pallas grouped-product kernels
    "moe_grouped_dispatch_xla": 0,    # moe_ffn_dropless call sites traced onto jax.lax.ragged_dot
    "moe_combine_gather": 0,          # moe_ffn_dropless row buckets traced moving rows by gathers through the inverse sort
    "moe_combine_scatter": 0,         # moe_ffn_dropless row buckets traced moving rows by scatter-adds
    "elastic_restart": 0,             # supervisor job re-formations
    "collective_timeout": 0,          # collective-watchdog expiries
    "snapshot_commit_ms": 0,          # two-phase run-snapshot commit wall ms
    "compile_total": 0,               # jit compilations across every site
    "compile_ms_total": 0,            # wall ms those compilations cost
    "recompile_steady_state": 0,      # compiles after the guard armed
    "memory_oom_postmortem": 0,       # OOM/budget-breach postmortems emitted
    "memory_budget_refusal": 0,       # admissions deferred by a MemoryBudget
    "goodput_snapshot": 0,            # goodput_snapshot() captures taken
    "goodput_downtime_ms": 0,         # downtime ms recorded into the ledger
}
_counter_lock = _threading.Lock()

# Optional per-reason breakdowns hanging off a declared counter
# (``incr_labeled``): {name: {label: n}}.  The flat counter stays the
# aggregate the dashboards alert on; the labels say WHY — e.g.
# ``step_fold_fallback`` splits by env-off / capture-failure /
# unsupported-optimizer / async-PS / grad-req-add so a silently-eager
# fold is diagnosable from one scrape (docs/observability.md).
_counter_labels = {}


def declare_counter(name, initial=0):
    """Register an extension counter so ``incr(name)`` is legal.  In-tree
    counters live in the ``_counters`` literal above; out-of-tree
    instrumentation (plugins, experiments) must declare before counting."""
    with _counter_lock:
        _counters.setdefault(name, initial)


def incr(name, n=1):
    # locked: the engine supports concurrent per-thread bulk queues, and a
    # bare read-modify-write would drop increments across threads (tests
    # pin exact counts); ~100ns next to a ~10us dispatch.  STRICT: an
    # undeclared name raises instead of silently creating a key that
    # reports zeros forever (the old .get(name, 0) behavior).
    with _counter_lock:
        try:
            _counters[name] += n
        except KeyError:
            raise KeyError(
                f"undeclared profiler counter {name!r}; add it to "
                f"profiler._counters or call declare_counter() first"
            ) from None


def incr_labeled(name, label, n=1):
    """Increment a declared counter AND its per-reason label breakdown
    (see ``counter_labels``).  Same strictness as :func:`incr` on the
    counter name; labels are free-form strings, created on first use —
    they classify events within a declared counter, they are not
    counters themselves (and stay out of the lint_counters doc table)."""
    label = str(label)
    with _counter_lock:
        try:
            _counters[name] += n
        except KeyError:
            raise KeyError(
                f"undeclared profiler counter {name!r}; add it to "
                f"profiler._counters or call declare_counter() first"
            ) from None
        lab = _counter_labels.setdefault(name, {})
        lab[label] = lab.get(label, 0) + n


def counter_labels(name=None):
    """Per-reason breakdowns recorded via :func:`incr_labeled`:
    ``{counter: {label: n}}`` (or one counter's ``{label: n}`` when
    ``name`` is given).  A label's sum never exceeds its flat counter —
    plain ``incr`` calls on the same counter carry no label."""
    with _counter_lock:
        if name is not None:
            return dict(_counter_labels.get(name, {}))
        return {k: dict(v) for k, v in _counter_labels.items()}


def counters():
    """Snapshot of the dispatch/bulking counters (parity-adjacent to the
    reference's engine op counters; see docs/observability.md)."""
    with _counter_lock:
        return dict(_counters)


def reset_counters():
    with _counter_lock:
        for k in _counters:
            _counters[k] = 0
        _counter_labels.clear()


# ---------------------------------------------------------------------------
# Process identity + clock alignment (ISSUE 7 multi-rank aggregation)
# ---------------------------------------------------------------------------

# Per-process metadata stamped into every dump()/metrics snapshot so a
# cluster's N traces can be told apart and re-aligned.  ``clock_offset_s``
# is THIS process's wall clock minus the cluster reference clock (rank 0 /
# the PS): corrected_unix = local_unix - clock_offset_s.  Offsets come
# from midpoint-of-RTT sampling (NTP's core trick): read local wall time
# around a fetch of the reference's wall time and attribute the reply to
# the midpoint; the min-RTT sample wins because its midpoint error is
# bounded by rtt/2.
_proc = {
    "rank": int(os.environ.get("DMLC_WORKER_ID", "0") or 0),
    "host": _socket.gethostname(),
    "pid": os.getpid(),
    "clock_offset_s": 0.0,
    "clock_rtt_s": None,   # RTT of the winning sample; None = never sampled
    "epoch_unix": _EPOCH_UNIX,
}


def process_info():
    """Copy of this process's identity/clock metadata (rank, host, pid,
    clock_offset_s, clock_rtt_s, epoch_unix)."""
    with _counter_lock:
        return dict(_proc)


def set_process_info(rank=None, host=None):
    """Pin this process's rank/host for traces and metrics (the dist
    kvstore tiers call this at bootstrap; DMLC_WORKER_ID is the default)."""
    with _counter_lock:
        if rank is not None:
            _proc["rank"] = int(rank)
        if host is not None:
            _proc["host"] = str(host)


def update_clock_offset(offset_s, rtt_s):
    """Record one clock-offset sample (local wall minus reference wall,
    attributed to the RTT midpoint).  The min-RTT sample of the process
    lifetime wins — its midpoint error bound (rtt/2) is the tightest."""
    with _counter_lock:
        best = _proc["clock_rtt_s"]
        if best is None or rtt_s < best:
            _proc["clock_offset_s"] = float(offset_s)
            _proc["clock_rtt_s"] = float(rtt_s)


def sample_clock_offset(fetch_ref_time, samples=5):
    """Estimate this process's wall-clock offset against a reference by
    midpoint-of-RTT sampling: ``fetch_ref_time()`` must return the
    reference's ``time.time()`` (e.g. a ``("clock",)`` request to the
    async PS).  Records the winning sample via ``update_clock_offset``
    and returns ``(offset_s, rtt_s)``."""
    best = None
    for _ in range(max(1, int(samples))):
        t0 = time.time()
        ref = fetch_ref_time()
        t1 = time.time()
        if ref is None:
            continue  # pre-ISSUE-7 peer: no wall time on the wire
        rtt = t1 - t0
        off = (t0 + t1) / 2.0 - float(ref)
        if best is None or rtt < best[1]:
            best = (off, rtt)
    if best is not None:
        update_clock_offset(*best)
    return best


# ---------------------------------------------------------------------------
# Span recorder (per-thread ring buffers)
# ---------------------------------------------------------------------------

# Fast gates read by the instrumentation sites (one module-attr read + a
# branch on the disabled path — the <3% overhead budget of ISSUE 5):
#   _recording  — spans go to the ring buffers (armed by start())
#   _telemetry  — step buckets accumulate (slow-step knob without a trace)
#   _active     — _recording or _telemetry; THE pre-check hot paths use
_recording = False
_telemetry = os.environ.get("MXNET_PROFILER_SLOW_STEP_MS") is not None
_active = _recording or _telemetry

_rings = []     # every live _Ring of the current recording generation
_ring_gen = 0   # bumped by start(): stale TLS rings are abandoned
_tls = _threading.local()

# step-bucket attribution: only ROOT spans count (nested phases like
# bulk.trace/bulk.execute or per-bucket kvstore.pushpull-inside-
# bucketed_pushpull would double-bill their parent's time)
_BUCKET_OF = {
    "dispatch.cache_hit": "host",
    "dispatch.jit_compile": "host",
    "dispatch.fallback": "host",
    "dispatch.raw": "host",
    "dispatch.backward": "host",
    "bulk.flush": "host",
    "fused.group_apply": "host",
    "io.wait": "host",           # consumer stalled on the infeed buffer —
                                 # host time the step critically paid
    "spmd.shard_batch": "host",  # per-step host->device transfer on the
                                 # consumer thread (what DataPipeline
                                 # exists to remove from the step)
    "kvstore.pushpull": "comms",
    "kvstore.push": "comms",
    "kvstore.pull": "comms",
}

# run-level goodput attribution (ISSUE 20): the same ROOT-span discipline
# as _BUCKET_OF, but folding spans into the RUN ledger's exclusive
# overhead buckets instead of the per-step host/comms split.  Precedence
# rules for overlapping spans (documented in docs/observability.md):
#
# * ``dispatch.jit_compile`` is deliberately ABSENT — its wall is covered
#   by the ``compile.jit`` span ``record_compile`` emits for every site
#   (kvstore-tier AND spmd/fold), so compile time lands in "compile"
#   exactly once instead of once in "host" and again in "compile";
# * ``kvstore.bucketed_pushpull`` is absent for the same reason its
#   children carry the _BUCKET_OF billing: the per-bucket
#   ``kvstore.pushpull`` leaves inside it would double-bill the parent;
# * only spans from the step-driving thread bill (a background prefetch
#   worker's dispatch overlaps the run on the wall clock — billing it
#   would break the buckets-sum-to-wall invariant the ledger exists for).
_GOODPUT_BUCKET_OF = {
    "dispatch.cache_hit": "host",
    "dispatch.fallback": "host",
    "dispatch.raw": "host",
    "dispatch.backward": "host",
    "bulk.flush": "host",
    "fused.group_apply": "host",
    "spmd.shard_batch": "host",
    "io.wait": "data_wait",
    "kvstore.pushpull": "comm",
    "kvstore.push": "comm",
    "kvstore.pull": "comm",
    "compile.jit": "compile",
    "elastic.snapshot": "checkpoint",
    "elastic.restore": "checkpoint",
}


_ring_uid = 0  # unique chrome-trace tid per ring: OS thread idents are
               # recycled, and reusing one would merge distinct (dead)
               # threads onto a single trace row


class _Ring:
    """Fixed-capacity per-thread span buffer.  Only the owner thread
    writes; ``snapshot()`` from the dump thread rides the GIL (list slot
    assignment is atomic — a racing write can at worst duplicate/omit the
    newest span, never tear one)."""

    __slots__ = ("buf", "cap", "pos", "count", "dropped", "tid", "tname",
                 "gen", "owner")

    def __init__(self, cap, gen):
        global _ring_uid
        self.cap = max(1, int(cap))
        self.buf = [None] * self.cap
        self.pos = 0
        self.count = 0
        self.dropped = 0
        _ring_uid += 1          # caller holds _counter_lock (or import)
        self.tid = _ring_uid
        thread = _threading.current_thread()
        self.tname = thread.name
        # weakref, not ident: idents recycle the moment a joined thread's
        # stack is reused, which would make its dead ring look alive
        self.owner = _weakref.ref(thread)
        self.gen = gen

    def dead(self):
        t = self.owner()
        return t is None or not t.is_alive()

    def add(self, ev):
        p = self.pos
        self.buf[p] = ev
        self.pos = (p + 1) % self.cap
        if self.count < self.cap:
            self.count += 1
        else:
            self.dropped += 1

    def snapshot(self):
        """Spans in chronological (insertion) order."""
        if self.count < self.cap:
            return self.buf[:self.count]
        p = self.pos
        return self.buf[p:] + self.buf[:p]


# retained-rings cap: dead threads' rings survive for dump() (a prefetch
# worker that exited mid-session recorded real spans), but under thread
# churn (a fresh worker per epoch) retention must not grow without bound
_MAX_RINGS = 64
_evicted = [0, 0]  # spans, dropped carried by evicted dead rings


def _ring():
    r = getattr(_tls, "ring", None)
    if r is None or r.gen != _ring_gen:
        with _counter_lock:
            r = _Ring(_config["ring_size"], _ring_gen)
            _tls.ring = r
            _rings.append(r)
            if len(_rings) > _MAX_RINGS:
                for x in [x for x in _rings
                          if x.dead() and x is not _step_ring][
                        :len(_rings) - _MAX_RINGS]:
                    # oldest dead rings evicted first; their spans leave
                    # the trace but stay visible in the dropped tally
                    _evicted[0] += x.count
                    _evicted[1] += x.dropped
                    _rings.remove(x)
    return r


_step_ring = None  # dedicated virtual timeline for the per-step spans: a
                   # user scope may legitimately straddle a step boundary,
                   # and a step span sharing the user thread's row would
                   # then partially overlap it and break B/E nesting


def _get_step_ring():
    global _step_ring
    with _counter_lock:
        if _step_ring is None or _step_ring.gen != _ring_gen:
            r = _Ring(_config["ring_size"], _ring_gen)
            r.tname = "steps (telemetry)"
            _rings.append(r)
            _step_ring = r
        return _step_ring


def recording_enabled():
    return _recording


def recorder_stats():
    """Occupancy of the span recorder: per-generation totals of recorded
    and ring-evicted (dropped-oldest) spans."""
    with _counter_lock:
        rings = list(_rings)
        ev_spans, ev_dropped = _evicted
    return {
        "recording": _recording,
        "threads": len(rings),
        "spans": sum(r.count for r in rings),
        "dropped": sum(r.dropped for r in rings) + ev_spans + ev_dropped,
        "ring_size": _config["ring_size"],
    }


def record_span(name, category, t0, t1=None, args=None, step=None):
    """Record one completed span.  ``t0``/``t1`` are ``time.perf_counter()``
    readings (``t1`` defaults to now); ``step`` defaults to the current
    step id.  Cheap no-op when neither the recorder nor telemetry is armed.

    RING ONLY, and post-hoc: for what is not a measured interval on the
    calling thread — ``compile.jit``, the ``step`` telemetry row, instant
    markers, and MODELED windows such as ``pipeline.stage``, which must
    never be written beside real device events.  A measured interval is a
    :class:`span`, which also reaches the device trace."""
    if not _active:
        return
    if t1 is None:
        t1 = _perf()
    if t0 < _armed_at:
        # a span straddling the arming instant (e.g. a scope entered
        # before start()) is clamped to the armed window: a B timestamp
        # predating every other recorded span would partially overlap
        # them and break chrome-trace duration nesting
        t0 = _armed_at
        if t1 < t0:
            t1 = t0
    bucket = _BUCKET_OF.get(name)
    gbucket = _GOODPUT_BUCKET_OF.get(name)
    if ((bucket is not None or gbucket is not None)
            and _threading.get_ident() == _step_thread):
        # only the step-owning thread bills the step buckets: a background
        # io-prefetch worker's dispatch spans overlap the step on the wall
        # clock and would inflate host_ms past what the step critically
        # paid (its spans still land in the trace below)
        with _counter_lock:
            if bucket is not None:
                _step_acc[bucket] = _step_acc.get(bucket, 0.0) + (t1 - t0)
            if gbucket is not None:
                _goodput_acc[gbucket] = (
                    _goodput_acc.get(gbucket, 0.0) + (t1 - t0))
    if _recording:
        # t1 stored raw (not as a duration): serialization derives begin
        # and end timestamps through the SAME float pipeline, so spans
        # sharing a boundary instant (adjacent step spans) stay exactly
        # equal and B/E pairing cannot invert across the boundary
        _ring().add((name, category, t0, t1,
                     _step_id if step is None else step, args))


class span:
    """``with profiler.span('fwd', 'user', {'step': 3}):`` — THE way a hot
    path times an interval on its own thread.  One span, two sinks:

    * always a ``jax.profiler.TraceAnnotation(name, **args)``: whenever any
      JAX trace session is on (``mx.profiler.start()``, a bare
      ``jax.profiler.start_trace``, a profiler server) the span lands on the
      ``/host:CPU`` plane of the same ``.xplane.pb`` as the device
      operations, on their clock, nested under the thread's open spans.
      With no session on it costs about a microsecond, which no switch
      could save;
    * when the recorder or telemetry is armed, the ring (chrome trace,
      step / goodput buckets) exactly as :func:`record_span`, stamped with
      the step id current at ENTRY (a span may contain its step boundary).
    """

    __slots__ = ("_name", "_cat", "_args", "_t0", "_step", "_ann")

    def __init__(self, name, category="user", args=None):
        self._name = name
        self._cat = category
        self._args = args
        self._ann = (_TraceAnnotation(name, **args) if args
                     else _TraceAnnotation(name))

    def __enter__(self):
        self._ann.__enter__()
        self._step = _step_id
        self._t0 = _perf()
        return self

    def __exit__(self, *a):
        if _active:
            record_span(self._name, self._cat, self._t0, args=self._args,
                        step=self._step)
        self._ann.__exit__(*a)
        return False


# ---------------------------------------------------------------------------
# Per-step telemetry
# ---------------------------------------------------------------------------

_step_id = 1          # spans inherit this; Trainer.step boundaries advance it
_step_t0 = None       # perf_counter at the current step's start (None =
                      # recorder armed mid-step: first boundary only anchors)
_step_thread = _threading.get_ident()   # thread whose spans bill the step
                                        # buckets; re-pinned per boundary
_armed_at = 0.0       # perf_counter of the last _arm(): spans straddling
                      # it are clamped so the trace nests validly
_step_acc = {"host": 0.0, "comms": 0.0}   # current step's bucket sums
_step_window = []     # list of per-step stat dicts, capped at step_window
_mem_watermark = {}   # device str -> peak bytes_in_use observed
_devices_cache = None


def current_step():
    """The step id spans currently inherit (monotone; advanced by
    ``step_boundary``)."""
    return _step_id


def step_stats():
    """Rolling window of per-step telemetry dicts
    (``step``/``wall_ms``/``host_ms``/``comms_ms``/``device_ms``)."""
    with _counter_lock:
        return [dict(s) for s in _step_window]


def memory_watermark():
    """Peak ``bytes_in_use`` observed per device (empty when the backend
    exposes no ``memory_stats``, e.g. CPU).  Sampled at step boundaries,
    on every ``metrics_snapshot()``, and on serving/generation/pipeline
    scheduler ticks — a serving-only process (no trainer steps) still
    reports a live watermark."""
    with _counter_lock:
        return dict(_mem_watermark)


def device_memory_stats(devices=None):
    """THE shared ``Device.memory_stats()`` probe (one parse rule for the
    whole repo — the watermark sampler, the io-pipeline pressure backoff,
    ``util.get_gpu_memory`` and ``config.memory_info`` all read through
    it).  Returns ``{device_str: {"bytes_in_use", "peak_bytes_in_use",
    "bytes_limit"}}``; devices that expose no stats (CPU) are simply
    absent.  Never raises."""
    global _devices_cache
    out = {}
    try:
        if devices is None:
            if _devices_cache is None:
                _devices_cache = jax.local_devices()
            devices = _devices_cache
        for d in devices:
            ms = getattr(d, "memory_stats", None)
            try:
                stats = ms() if callable(ms) else None
            except Exception:
                stats = None
            if not stats:
                continue
            used = int(stats.get("bytes_in_use", 0) or 0)
            out[str(d)] = {
                "bytes_in_use": used,
                "peak_bytes_in_use": int(
                    stats.get("peak_bytes_in_use", used) or used),
                "bytes_limit": int(stats.get("bytes_limit", 0) or 0),
            }
    except Exception:
        pass  # telemetry must never take training down
    return out


# memory counter-track samples for the chrome trace: (perf_t,
# {device: bytes_in_use}, {category: ledger_bytes}); bounded FIFO,
# cleared per fresh recording session
_mem_samples = []
_MAX_MEM_SAMPLES = _env_int("MXNET_PROFILER_MEM_SAMPLES", 4096)
_mem_last = [0.0]   # perf_counter of the last sample (throttle)


def sample_device_memory():
    """Take one device-memory sample: update the per-device watermark and
    (while the recorder is armed) append a counter-track point carrying
    per-device ``bytes_in_use`` plus the ledger's per-category totals —
    ``dump()`` serializes these as chrome-trace ``C`` events, which
    Perfetto renders as a memory timeline.  No-op when
    ``set_config(memory_sampling=False)``."""
    if not _config.get("memory_sampling", True):
        return
    now = _perf()
    _mem_last[0] = now
    stats = device_memory_stats()
    dev_use = {}
    with _counter_lock:
        for key, s in stats.items():
            dev_use[key] = s["bytes_in_use"]
            used = s["peak_bytes_in_use"]
            if used > _mem_watermark.get(key, -1):
                _mem_watermark[key] = used
    if _recording:
        cats = _ledger_categories()
        if dev_use or cats:
            with _counter_lock:
                _mem_samples.append((now, dev_use, cats))
                while len(_mem_samples) > _MAX_MEM_SAMPLES:
                    _mem_samples.pop(0)


# back-compat alias: the pre-ISSUE-12 step-boundary sampler
_sample_memory = sample_device_memory


def maybe_sample_memory(min_interval_s=None):
    """Throttled :func:`sample_device_memory` — the scheduler-tick entry
    (serving dispatch, generation iteration, pipeline transfer,
    ``metrics_snapshot``).  Samples at most every
    ``MXNET_PROFILER_MEM_SAMPLE_S`` seconds (default 0.05) so a hot
    serving loop never turns telemetry into a hot path."""
    if not _config.get("memory_sampling", True):
        return
    if min_interval_s is None:
        min_interval_s = _env_float("MXNET_PROFILER_MEM_SAMPLE_S", 0.05)
    if _perf() - _mem_last[0] < min_interval_s:
        return
    sample_device_memory()


def _slow_threshold_ms():
    """Explicit slow-step threshold, or None for auto mode.  Config wins
    over the env (set_config is the runtime control surface)."""
    v = _config.get("slow_step_ms")
    if v is None:
        env = os.environ.get("MXNET_PROFILER_SLOW_STEP_MS")
        if env:
            try:
                v = float(env)
            except ValueError:
                v = None
    if v is not None and v <= 0:
        # 0 = off, matching the repo's env-knob convention
        # (MXNET_OPTIMIZER_AGGREGATION=0 etc.); auto mode stays off too
        # because an explicit threshold was configured
        return float("inf")
    return v


def _median(xs):
    xs = sorted(xs)
    n = len(xs)
    return xs[n // 2] if n % 2 else 0.5 * (xs[n // 2 - 1] + xs[n // 2])


def percentile(xs, q):
    """Nearest-rank percentile (the serving tier's latency convention);
    None on empty input.  THE shared helper — the serving/generation
    servers and the opperf harnesses all quote percentiles through it so
    one method governs every p50/p99 the repo reports."""
    if not xs:
        return None
    xs = sorted(xs)
    return xs[min(len(xs) - 1, int(q * len(xs)))]


_slow_step_annotators = {}   # key -> fn(step_stats_dict) -> str | None


def register_slow_step_annotator(key, fn):
    """Attach a subsystem attribution line to the slow-step detector:
    when a step trips the threshold, every registered annotator is called
    with that step's stats dict and a truthy return is logged as ONE
    extra line (``slow step N <key>: <line>``).  The pipeline tier uses
    this to name the straggling stage the way ``straggler_report`` names
    the straggling rank.  Re-registering a key replaces the annotator."""
    with _counter_lock:
        _slow_step_annotators[str(key)] = fn


def unregister_slow_step_annotator(key):
    with _counter_lock:
        _slow_step_annotators.pop(str(key), None)


def step_boundary():
    """Close the current telemetry step (called by ``gluon.Trainer.step``
    and ``SPMDTrainer.step``; safe to call directly from custom loops).

    Records the whole-step span, splits its wall time into host-dispatch /
    comms / device buckets from the spans seen since the previous
    boundary, feeds the rolling window + slow-step detector, samples
    device-memory watermarks, and advances the step id every subsequent
    span inherits.  No-op while the profiler is inactive."""
    global _step_id, _step_t0, _step_thread
    _guard_tick()  # compile-guard warmup countdown is tracing-independent
    if not _active:
        return
    now = _perf()
    _step_thread = _threading.get_ident()  # whoever drives steps owns them
    with _counter_lock:
        sid = _step_id
        t0 = _step_t0
        _step_t0 = now
        host = _step_acc.get("host", 0.0)
        comms = _step_acc.get("comms", 0.0)
        _step_acc["host"] = 0.0
        _step_acc["comms"] = 0.0
        _step_id = sid + 1
    if t0 is None:
        return  # armed mid-step: this boundary only anchors the next one
    wall = now - t0
    if _recording:
        # straight onto the dedicated step timeline (adjacent step spans
        # never overlap there; user-thread spans may straddle boundaries)
        ring = _get_step_ring()
        with _counter_lock:
            ring.add(("step", "step", max(t0, _armed_at), now, sid,
                      {"host_ms": round(host * 1e3, 3),
                       "comms_ms": round(comms * 1e3, 3)}))
    # host/comms are raw span sums (concurrent threads can legitimately
    # exceed wall); only the derived device/other residue is clamped
    wall_ms = wall * 1e3
    host_ms = host * 1e3
    comms_ms = comms * 1e3
    device_ms = max(0.0, wall_ms - host_ms - comms_ms)
    stats = {"step": sid, "wall_ms": wall_ms, "host_ms": host_ms,
             "comms_ms": comms_ms, "device_ms": device_ms}

    thr = _slow_threshold_ms()
    slow, why = False, ""
    with _counter_lock:
        prior = [s["wall_ms"] for s in _step_window]
        _step_window.append(stats)
        limit = int(_config.get("step_window", 256))
        while len(_step_window) > limit:
            _step_window.pop(0)
    if thr is not None:
        if wall_ms > thr:
            slow, why = True, f"threshold {thr:g} ms"
    elif _config.get("slow_step_auto", True) and len(prior) >= 16:
        med = _median(prior)
        mult = float(_config.get("slow_step_auto_mult", 4.0))
        if med > 0 and wall_ms > mult * med:
            slow, why = True, f"auto: > {mult:g}x rolling median {med:.1f} ms"
    if slow:
        incr("slow_step_detected")
        _logger.warning(
            "slow step %d: %.1f ms (host-dispatch %.1f ms, comms %.1f ms, "
            "device/other %.1f ms) [%s]",
            sid, wall_ms, host_ms, comms_ms, device_ms, why)
        # subsystem attribution: registered annotators (the pipeline tier
        # names its busiest stage the way straggler_report names the
        # slowest rank) — EXACTLY one extra line per annotator per
        # anomalous step, and a broken annotator never takes training down
        with _counter_lock:
            annots = list(_slow_step_annotators.items())
        for key, fn in annots:
            try:
                line = fn(dict(stats))
            except Exception:
                line = None
            if line:
                _logger.warning("slow step %d %s: %s", sid, key, line)
        # cross-rank attribution: when peers' metrics snapshots are in the
        # registry (heartbeat piggyback / scrape aggregation), name the
        # slowest rank — EXACTLY one line per anomalous step, guarded by
        # this branch firing once per boundary
        rep = straggler_report()
        if rep is not None:
            incr("straggler_detected")
            _logger.warning(
                "slow step %d straggler: rank %d (%s) — step %s wall "
                "%.1f ms (host-dispatch %.1f ms, comms %.1f ms, "
                "device/other %.1f ms)",
                sid, rep["rank"], rep["host"], rep["step"], rep["wall_ms"],
                rep["host_ms"], rep["comms_ms"], rep["device_ms"])
    if _config.get("memory_sampling", True):
        _sample_memory()


# ---------------------------------------------------------------------------
# Live metrics export (ISSUE 7): registry, JSONL log, Prometheus endpoint
# ---------------------------------------------------------------------------

_metrics_seq = 0       # monotone per-process snapshot sequence number
_peer_metrics = {}     # rank -> latest snapshot published by that rank
_metrics_providers = {}  # key -> fn() -> flat {field: number} dict


def register_metrics_provider(key, fn):
    """Attach a subsystem gauge source to ``metrics_snapshot()``: ``fn``
    must return a flat ``{field: number}`` dict, captured under
    ``snapshot["providers"][key]`` and rendered by the Prometheus endpoint
    as ``mxnet_<key>_<field>`` gauges.  The serving tier registers its
    queue depth / latency percentiles here so every export surface
    (JSONL, /metrics, heartbeat piggyback) carries serving health for
    free.  Re-registering a key replaces the previous provider."""
    with _counter_lock:
        _metrics_providers[str(key)] = fn


def register_metrics_provider_unique(base, fn):
    """Register ``fn`` under ``base``, or ``base2``/``base3``/... if the
    name is taken — probe and insert under ONE lock acquisition, so two
    subsystems registering concurrently cannot race the probe and
    silently replace each other (plain ``register_metrics_provider``
    overwrites on collision by design).  Returns the chosen name, which
    the caller passes to ``unregister_metrics_provider`` later."""
    base = str(base)
    with _counter_lock:
        name, n = base, 2
        while name in _metrics_providers:
            name, n = f"{base}{n}", n + 1
        _metrics_providers[name] = fn
    return name


def unregister_metrics_provider(key):
    """Detach a provider (``InferenceServer.close`` calls this so a dead
    server's frozen gauges leave the scrape surface)."""
    with _counter_lock:
        _metrics_providers.pop(str(key), None)


def _provider_metrics():
    with _counter_lock:
        providers = dict(_metrics_providers)
    out = {}
    for key, fn in providers.items():
        try:
            d = fn()
        except Exception:
            continue  # telemetry must never take serving down
        if isinstance(d, dict):
            out[key] = {str(k): v for k, v in d.items()
                        if isinstance(v, (int, float)) or v is None}
    return out


def metrics_snapshot():
    """One self-describing metrics capture: process identity, counters,
    the step-telemetry window summary + last closed step's bucket split,
    and memory watermarks.  This dict IS the JSONL schema (one object per
    line; ``schema`` versions it) and the unit the cluster aggregates —
    heartbeats ship it to the PS, ``publish_peer_metrics`` registers it,
    the Prometheus endpoint renders it."""
    global _metrics_seq
    incr("metrics_snapshot")
    # sample device memory on the snapshot tick: a serving-only process
    # (no trainer step boundaries) must still report a live watermark
    maybe_sample_memory()
    with _counter_lock:
        _metrics_seq += 1
        seq = _metrics_seq
    steps = step_stats()
    walls = [s["wall_ms"] for s in steps]
    return {
        "schema": 1,
        "rank": _proc["rank"],
        "host": _proc["host"],
        "pid": _proc["pid"],
        "seq": seq,
        "time_unix": time.time(),
        "clock_offset_s": _proc["clock_offset_s"],
        "counters": counters(),
        "counter_labels": counter_labels(),
        "last_step": dict(steps[-1]) if steps else None,
        "window": {
            "n": len(steps),
            "wall_ms_median": _median(walls) if walls else None,
            "wall_ms_max": max(walls) if walls else None,
        },
        "memory_watermark_bytes": memory_watermark(),
        "providers": _provider_metrics(),
    }


def publish_peer_metrics(snap):
    """Register a peer rank's metrics snapshot (called by the async PS on
    heartbeat receipt — the PS lives in rank 0's process, so rank 0's
    scrape surface sees the cluster).  Stale out-of-order snapshots from
    the SAME process are dropped; a restarted peer (new pid) always
    replaces its predecessor."""
    if not isinstance(snap, dict) or "rank" not in snap:
        return
    rank = int(snap["rank"])
    with _counter_lock:
        old = _peer_metrics.get(rank)
        if (old is None or old.get("pid") != snap.get("pid")
                or snap.get("seq", 0) >= old.get("seq", 0)):
            _peer_metrics[rank] = dict(snap)


def peer_metrics():
    """Snapshot of the peer-metrics registry: ``{rank: snapshot}``."""
    with _counter_lock:
        return {r: dict(s) for r, s in _peer_metrics.items()}


def forget_peer_metrics(rank):
    """Drop a departed rank's snapshot (the async PS calls this on
    deregister/eviction so a dead rank's frozen numbers leave the scrape
    surface and the straggler comparison instead of haunting them)."""
    with _counter_lock:
        _peer_metrics.pop(int(rank), None)


def _cluster_snapshots():
    """Local snapshot first, then peers by rank.  On a rank clash the
    local snapshot wins (rank 0 heartbeats against its own co-located PS,
    so its snapshot legitimately appears on both sides) — UNLESS the
    clash is a different process with real step telemetry while the local
    one is idle: that is the standalone-PS case (the PS process defaults
    to rank 0 while worker 0 heartbeats), where the training process's
    numbers are the ones a scrape is after."""
    local = metrics_snapshot()
    rows = [local]
    for rank, snap in sorted(peer_metrics().items()):
        if rank != local["rank"]:
            rows.append(snap)
        elif (snap.get("pid") != local.get("pid")
                and local.get("last_step") is None
                and snap.get("last_step") is not None):
            rows[0] = snap
    return rows


def _prom_escape(v):
    return str(v).replace("\\", "\\\\").replace('"', '\\"').replace("\n", " ")


def render_prometheus():
    """All known snapshots (local + peers) as Prometheus text (exposition
    format 0.0.4): counters, per-rank step buckets, rolling-window
    summary, memory watermarks, clock offsets."""
    out = [
        "# HELP mxnet_profiler_counter_total profiler event counters "
        "(see docs/observability.md counter reference)",
        "# TYPE mxnet_profiler_counter_total counter",
    ]
    gauges = []  # (name, help) emitted after the counter block
    g_lines = {}

    def gauge(name, help_, labels, value):
        if value is None:
            return
        if name not in g_lines:
            gauges.append((name, help_))
            g_lines[name] = []
        lab = ",".join(f'{k}="{_prom_escape(v)}"' for k, v in labels)
        g_lines[name].append(f"{name}{{{lab}}} {value}")

    for snap in _cluster_snapshots():
        base = (("rank", snap.get("rank")), ("host", snap.get("host", "?")))
        for cname, v in sorted((snap.get("counters") or {}).items()):
            lab = ",".join(f'{k}="{_prom_escape(v2)}"' for k, v2 in
                           (("counter", cname),) + base)
            out.append(f"mxnet_profiler_counter_total{{{lab}}} {v}")
        for cname, labs in sorted((snap.get("counter_labels")
                                   or {}).items()):
            for reason, v in sorted((labs or {}).items()):
                lab = ",".join(
                    f'{k}="{_prom_escape(v2)}"' for k, v2 in
                    (("counter", cname), ("reason", reason)) + base)
                out.append(f"mxnet_profiler_counter_total{{{lab}}} {v}")
        ls = snap.get("last_step") or {}
        gauge("mxnet_step_last_id", "id of the last closed step",
              base, ls.get("step"))
        for bucket in ("wall_ms", "host_ms", "comms_ms", "device_ms"):
            gauge(f"mxnet_step_last_{bucket}",
                  f"last closed step {bucket.replace('_', ' ')} split",
                  base, ls.get(bucket))
        win = snap.get("window") or {}
        gauge("mxnet_step_window_n", "steps in the rolling telemetry window",
              base, win.get("n"))
        gauge("mxnet_step_wall_ms_median", "rolling-window median step wall",
              base, win.get("wall_ms_median"))
        gauge("mxnet_step_wall_ms_max", "rolling-window max step wall",
              base, win.get("wall_ms_max"))
        gauge("mxnet_clock_offset_seconds",
              "estimated wall-clock offset vs the cluster reference",
              base, snap.get("clock_offset_s"))
        gauge("mxnet_metrics_snapshot_seq", "snapshot sequence number",
              base, snap.get("seq"))
        for dev, b in sorted((snap.get("memory_watermark_bytes")
                              or {}).items()):
            gauge("mxnet_memory_watermark_bytes",
                  "peak device bytes_in_use observed at step boundaries",
                  base + (("device", dev),), b)
        for pkey, fields in sorted((snap.get("providers") or {}).items()):
            for field, v in sorted((fields or {}).items()):
                gauge(f"mxnet_{pkey}_{field}",
                      f"{pkey} subsystem gauge (registered metrics "
                      "provider; see docs/serving.md)",
                      base, v)
    for name, help_ in gauges:
        out.append(f"# HELP {name} {help_}")
        out.append(f"# TYPE {name} gauge")
        out.extend(g_lines[name])
    return "\n".join(out) + "\n"


class _MetricsExporter(_threading.Thread):
    """Periodic per-rank JSONL metrics log (append-only; one
    ``metrics_snapshot()`` object per line)."""

    def __init__(self, path, interval_s):
        super().__init__(name="mxtpu-metrics-exporter", daemon=True)
        self.path = path
        self.interval_s = max(0.05, float(interval_s))
        self.stop_event = _threading.Event()

    def run(self):
        while not self.stop_event.wait(self.interval_s):
            try:
                snap = metrics_snapshot()
                with open(self.path, "a") as f:
                    f.write(json.dumps(snap) + "\n")
            except Exception:
                pass  # telemetry must never take training down

    def stop(self):
        self.stop_event.set()


_metrics_http = None      # (ThreadingHTTPServer, serving thread)
_metrics_exporter = None  # _MetricsExporter
_metrics_lock = _threading.Lock()

# process health for the /healthz endpoint: "serving" (200) until a
# graceful drain begins (serving.install_sigterm_drain), then "draining"
# (503) so external load balancers stop routing here before in-flight
# work finishes
_health = "serving"


def set_health(state):
    """Set the process health reported by ``/healthz`` ("serving" → 200,
    anything else → 503 with the state in the body)."""
    global _health
    _health = str(state)


def health_state():
    return _health


def _make_metrics_handler():
    from http.server import BaseHTTPRequestHandler

    class Handler(BaseHTTPRequestHandler):
        def do_GET(self):
            incr("metrics_scrape")
            path = self.path.split("?", 1)[0]
            if path in ("/", "/metrics"):
                body = render_prometheus().encode()
                ctype = "text/plain; version=0.0.4; charset=utf-8"
            elif path == "/metrics.json":
                body = json.dumps({"local": metrics_snapshot(),
                                   "peers": {str(r): s for r, s in
                                             peer_metrics().items()}}).encode()
                ctype = "application/json"
            elif path == "/healthz":
                # load-balancer health check: 200 only while serving —
                # a draining process must leave rotation immediately,
                # even though /metrics keeps answering 200
                state = health_state()
                body = (state + "\n").encode()
                self.send_response(200 if state == "serving" else 503)
                self.send_header("Content-Type", "text/plain; charset=utf-8")
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)
                return
            else:
                self.send_error(404)
                return
            self.send_response(200)
            self.send_header("Content-Type", ctype)
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

        def log_message(self, *a):  # scrapes must not spam stderr
            pass

    return Handler


def start_metrics(port=None, jsonl=None, interval_s=None):
    """Start the live metrics surface: a Prometheus ``/metrics`` endpoint
    (+ ``/metrics.json``) and/or a periodic per-rank JSONL log.

    ``port=None`` reads ``MXNET_METRICS_PORT`` (0/unset = no endpoint,
    the repo's env-knob convention); an explicit ``port=0`` binds an
    OS-assigned ephemeral port (tests; read it back via
    ``metrics_server_port()``).  ``jsonl=None`` reads
    ``MXNET_METRICS_JSONL`` (unset = no log); the interval comes from
    ``MXNET_METRICS_INTERVAL_S`` (default 10 s).  A port already taken
    (two local ranks sharing one env) warns once and serves nothing —
    the surviving binder is the scrape target.  Idempotent per surface."""
    global _metrics_http, _metrics_exporter
    env_port = port is None
    if env_port:
        try:
            port = int(os.environ.get("MXNET_METRICS_PORT", "0") or 0)
        except ValueError:
            port = 0
    if jsonl is None:
        jsonl = os.environ.get("MXNET_METRICS_JSONL") or None
    if interval_s is None:
        # guarded like the port parse: a typo'd knob degrades to the
        # default instead of raising at import (this runs env-driven at
        # module import — telemetry must never take training down)
        interval_s = _env_float("MXNET_METRICS_INTERVAL_S", 10.0)
    with _metrics_lock:
        if _metrics_http is None and (port > 0 or (port == 0 and not env_port)):
            from http.server import ThreadingHTTPServer

            try:
                srv = ThreadingHTTPServer(("", port), _make_metrics_handler())
                srv.daemon_threads = True
                th = _threading.Thread(target=srv.serve_forever,
                                       name="mxtpu-metrics-http", daemon=True)
                th.start()
                _metrics_http = (srv, th)
            except OSError as e:
                _warnings.warn(
                    f"metrics endpoint: cannot bind port {port} ({e}); "
                    "serving no metrics from this process (another local "
                    "rank probably owns the port)", RuntimeWarning,
                    stacklevel=2)
        if jsonl and _metrics_exporter is None:
            _metrics_exporter = _MetricsExporter(jsonl, interval_s)
            _metrics_exporter.start()
    return metrics_server_port()


def metrics_server_port():
    """Actual bound port of the live endpoint, or None when off."""
    with _metrics_lock:
        return _metrics_http[0].server_address[1] if _metrics_http else None


def stop_metrics():
    """Tear the metrics surface down (endpoint + JSONL exporter)."""
    global _metrics_http, _metrics_exporter
    with _metrics_lock:
        if _metrics_http is not None:
            srv, th = _metrics_http
            _metrics_http = None
            srv.shutdown()
            srv.server_close()
        if _metrics_exporter is not None:
            _metrics_exporter.stop()
            _metrics_exporter = None


# ---------------------------------------------------------------------------
# Cross-rank straggler attribution (ISSUE 7)
# ---------------------------------------------------------------------------


def straggler_report():
    """Compare the freshest per-rank step wall-times (local telemetry +
    peer snapshots) and return the slowest rank's breakdown::

        {"rank", "host", "step", "wall_ms", "host_ms", "comms_ms",
         "device_ms", "ranks_compared"}

    Returns None without at least two ranks' worth of step data (nothing
    to attribute ACROSS).  Peers' numbers are their last CLOSED step —
    ranks run asynchronously, so the compared step ids may differ; each
    row names its own.  Peer fields are read defensively (this runs
    inside ``step_boundary`` on the training hot path, and the heartbeat
    wire accepts any dict-shaped snapshot, including an older build's);
    snapshots older than ``MXNET_METRICS_PEER_TTL_S`` are ignored so a
    departed rank's frozen numbers cannot be blamed forever."""
    rows = []
    steps = step_stats()
    if steps:
        with _counter_lock:
            me = dict(rank=_proc["rank"], host=_proc["host"])
        rows.append({**me, **steps[-1]})
    now_ref = time.time() - _proc["clock_offset_s"]
    ttl = _env_float("MXNET_METRICS_PEER_TTL_S", 120.0)
    for rank, snap in sorted(peer_metrics().items()):
        if rows and rank == rows[0]["rank"]:
            continue
        ls = snap.get("last_step")
        if not isinstance(ls, dict) or "wall_ms" not in ls:
            continue
        t = snap.get("time_unix")
        if ttl > 0 and isinstance(t, (int, float)):
            # both sides corrected onto the reference clock before aging
            age = now_ref - (t - (snap.get("clock_offset_s") or 0.0))
            if age > ttl:
                continue
        rows.append({"rank": rank, "host": snap.get("host", "?"), **ls})
    if len(rows) < 2:
        return None
    worst = max(rows, key=lambda r: r.get("wall_ms", 0.0))
    return {"rank": worst["rank"], "host": worst["host"],
            "step": worst.get("step"), "wall_ms": worst.get("wall_ms", 0.0),
            "host_ms": worst.get("host_ms", 0.0),
            "comms_ms": worst.get("comms_ms", 0.0),
            "device_ms": worst.get("device_ms", 0.0),
            "ranks_compared": len(rows)}


# ---------------------------------------------------------------------------
# Goodput ledger (ISSUE 20): run-level wall-clock decomposition
# ---------------------------------------------------------------------------

# Where did the run's seconds go?  The per-step telemetry above answers
# that for ONE step; the goodput ledger answers it for the RUN: every
# armed second lands in exactly one bucket — compute (the residual),
# host dispatch, data wait, comm, compile, checkpoint, pipeline bubble,
# or elastic-restart downtime — accumulated from the spans/counters the
# repo already records (no new per-step probes).
#
# Scope: the ledger is RUN-scoped (process generation), not recording-
# session-scoped.  ``start()``/``stop()``/``pause()``/``resume()`` only
# open/close the wall-clock window it integrates over; only an explicit
# ``reset_goodput()`` zeroes it.  Downtime recorded by ``record_downtime``
# (the supervisor's restart gap, fed through ``MXNET_ELASTIC_DOWNTIME_S``)
# is added to BOTH its bucket and the wall — it happened while no
# profiler in this process could observe anything.
#
# Invariant: buckets sum to wall_s by construction (compute is the
# clamped residual), so ``goodput = compute / wall`` is a true fraction.

_goodput_acc = {"host": 0.0, "data_wait": 0.0, "comm": 0.0,
                "compile": 0.0, "checkpoint": 0.0, "downtime": 0.0}
_goodput_downtime = {}        # reason -> seconds (record_downtime detail)
_goodput_wall_s = 0.0         # closed armed windows, summed
_goodput_win_t0 = _perf() if _active else None  # open window start
_goodput_bubble_base_ms = 0   # pipeline_bubble_ms at the last reset

_GOODPUT_BUCKETS = ("compute", "host", "data_wait", "comm", "compile",
                    "checkpoint", "bubble", "downtime")


def _goodput_open(now=None):
    """Open the armed wall-clock window (idempotent)."""
    global _goodput_win_t0
    with _counter_lock:
        if _goodput_win_t0 is None:
            _goodput_win_t0 = _perf() if now is None else now


def _goodput_close(now=None):
    """Close the armed window, folding it into the wall total
    (idempotent)."""
    global _goodput_wall_s, _goodput_win_t0
    with _counter_lock:
        if _goodput_win_t0 is not None:
            _goodput_wall_s += (_perf() if now is None else now) \
                - _goodput_win_t0
            _goodput_win_t0 = None


def reset_goodput():
    """Zero the run ledger (tests; an explicit fresh measurement window).
    Re-baselines the bubble counter and reopens the wall window when the
    profiler is armed."""
    global _goodput_wall_s, _goodput_win_t0, _goodput_bubble_base_ms
    with _counter_lock:
        for k in _goodput_acc:
            _goodput_acc[k] = 0.0
        _goodput_downtime.clear()
        _goodput_wall_s = 0.0
        _goodput_win_t0 = _perf() if _active else None
        _goodput_bubble_base_ms = _counters.get("pipeline_bubble_ms", 0)


def record_downtime(seconds, reason="downtime"):
    """Account seconds this process generation did NOT exist (or could
    not train) into the ledger's downtime bucket — the supervisor's
    death→respawn gap, fed via ``MXNET_ELASTIC_DOWNTIME_S`` and consumed
    once by ``parallel.elastic.init()``.  Adds to both the bucket and the
    wall (the invariant: buckets sum to wall)."""
    seconds = float(seconds)
    if seconds <= 0:
        return
    reason = str(reason)
    with _counter_lock:
        _goodput_acc["downtime"] += seconds
        _goodput_downtime[reason] = (
            _goodput_downtime.get(reason, 0.0) + seconds)
    incr("goodput_downtime_ms", int(round(seconds * 1e3)))


def goodput_snapshot():
    """The run's wall-clock decomposition::

        {"schema", "rank", "host", "time_unix", "active", "wall_s",
         "goodput", "buckets_s": {compute, host, data_wait, comm,
         compile, checkpoint, bubble, downtime}, "overhead_s",
         "top_overhead", "downtime_detail"}

    ``wall_s`` integrates armed (``_active``) time plus recorded
    downtime; every bucket is exclusive (see docs/observability.md for
    the overlap-precedence rules) and ``compute`` is the clamped
    residual, so the buckets sum to ``wall_s`` by construction.
    ``goodput`` is compute/wall (None until any wall exists).  Schema-
    versioned like ``metrics_snapshot``; embedded in ``dump()``'s
    otherData and exported by the "goodput" metrics provider."""
    incr("goodput_snapshot")
    now = _perf()
    with _counter_lock:
        acc = dict(_goodput_acc)
        wall = _goodput_wall_s
        if _goodput_win_t0 is not None:
            wall += now - _goodput_win_t0
        bubble_ms = max(0, _counters.get("pipeline_bubble_ms", 0)
                        - _goodput_bubble_base_ms)
        detail = dict(_goodput_downtime)
        rank, host = _proc["rank"], _proc["host"]
        armed = _goodput_win_t0 is not None
    wall += acc["downtime"]  # the process did not exist: wall grows too
    buckets = {
        "host": acc["host"],
        "data_wait": acc["data_wait"],
        "comm": acc["comm"],
        "compile": acc["compile"],
        "checkpoint": acc["checkpoint"],
        "bubble": bubble_ms / 1e3,
        "downtime": acc["downtime"],
    }
    overhead = sum(buckets.values())
    buckets["compute"] = max(0.0, wall - overhead)
    buckets = {k: round(buckets[k], 6) for k in _GOODPUT_BUCKETS}
    top = sorted(((k, v) for k, v in buckets.items()
                  if k != "compute" and v > 0),
                 key=lambda kv: -kv[1])
    return {
        "schema": 1,
        "rank": rank,
        "host": host,
        "time_unix": time.time(),
        "active": armed,
        "wall_s": round(wall, 6),
        "goodput": round(buckets["compute"] / wall, 6) if wall > 0 else None,
        "buckets_s": buckets,
        "overhead_s": round(min(overhead, wall), 6),
        "top_overhead": [[k, v] for k, v in top[:3]],
        "downtime_detail": {k: round(v, 6) for k, v in detail.items()},
    }


def _goodput_provider():
    """Built-in "goodput" metrics provider: the ledger as flat gauges —
    rides every export surface (JSONL, /metrics as ``mxnet_goodput_*``,
    heartbeat piggyback) and is what ``cluster_goodput`` aggregates."""
    snap = goodput_snapshot()
    out = {"wall_s": snap["wall_s"], "goodput": snap["goodput"]}
    for k, v in snap["buckets_s"].items():
        out[f"{k}_s"] = v
    return out


register_metrics_provider("goodput", _goodput_provider)


def cluster_goodput():
    """Whole-job goodput over every known rank (local ledger + the peer
    snapshots the PR 6 heartbeat piggyback delivered to rank 0)::

        {"schema", "ranks", "wall_s", "goodput",
         "worst": {"rank", "host", "goodput", "bucket", "bucket_s"}}

    Job goodput is wall-weighted (sum of compute over sum of wall), the
    worst rank is the lowest-goodput one, and ``bucket`` names where its
    time went (its largest overhead bucket).  Returns None when no rank
    has any wall yet."""
    rows = []
    for snap in _cluster_snapshots():
        g = (snap.get("providers") or {}).get("goodput")
        if not isinstance(g, dict):
            continue
        wall = g.get("wall_s")
        if not isinstance(wall, (int, float)) or wall <= 0:
            continue
        rows.append((snap.get("rank", -1), snap.get("host", "?"), g))
    if not rows:
        return None
    tot_wall = sum(g["wall_s"] for _, _, g in rows)
    tot_compute = sum(g.get("compute_s") or 0.0 for _, _, g in rows)
    worst_rank, worst_host, worst = min(
        rows, key=lambda r: (r[2].get("goodput") is None,
                             r[2].get("goodput") or 0.0))
    over = [(k[:-2], v) for k, v in worst.items()
            if k.endswith("_s") and k not in ("wall_s", "compute_s")
            and isinstance(v, (int, float)) and v > 0]
    top = max(over, key=lambda kv: kv[1]) if over else (None, 0.0)
    return {
        "schema": 1,
        "ranks": len(rows),
        "wall_s": round(tot_wall, 6),
        "goodput": round(tot_compute / tot_wall, 6) if tot_wall > 0 else None,
        "worst": {"rank": worst_rank, "host": worst_host,
                  "goodput": worst.get("goodput"),
                  "bucket": top[0], "bucket_s": round(top[1], 6)},
    }


# ---------------------------------------------------------------------------
# Device-memory observability (ISSUE 12): live HBM ledger with per-subsystem
# attribution, OOM forensics, and budgeted admission
# ---------------------------------------------------------------------------

# The compile registry answers "what compiled"; this ledger answers "what
# OWNS the bytes".  Every subsystem that holds device buffers registers an
# owner via ``track_memory(owner, category)`` and accounts its allocations
# with plain integer deltas (``alloc``/``free``/``set``) — no device probe
# on the accounting path, so the ledger is exact for what is wired and
# free when nothing is.  Donation-aware by construction: a donated buffer
# is REPLACED by its same-shaped successor, so the owner's bytes never
# move on a fused optimizer step or a KV-cache decode.  On top of it:
#
# * ``MemoryBudget`` — the one admission API (``MXNET_MEM_BUDGET_MB`` or
#   an explicit per-subsystem cap); GenerationServer slot admission and
#   the DataPipeline autotuner consult it instead of raw memory_stats();
# * OOM forensics — the dispatch choke points (engine flush, SPMD step,
#   serving dispatch, stateful-executor/KV insert, fused optimizer step)
#   route ``RESOURCE_EXHAUSTED`` through :func:`maybe_oom_postmortem`,
#   which emits ONE structured report naming the top owners by bytes and
#   the failed allocation size before the error re-raises;
# * a per-device memory **counter track** in the chrome trace (Perfetto
#   renders a timeline), sampled at step boundaries, metrics snapshots
#   and serving/pipeline ticks; ``tools/trace_merge.py`` carries it
#   across ranks and ``tools/memory_report.py`` summarizes it offline.
#
# See docs/observability.md#device-memory-observability.

_mem_lock = _threading.Lock()
_mem_owners = {}        # owner name -> MemoryTracker (THE ledger)
_mem_postmortems = []   # bounded FIFO of postmortem report dicts
_MAX_POSTMORTEMS = 64


class MemoryTracker:
    """Owner-scoped accounting handle returned by :func:`track_memory`.

    ``alloc``/``free`` move bytes in and out of the owner's row;
    ``set`` pins an absolute total (sites that recompute their footprint
    rather than tracking deltas).  Handles are shared: a second
    ``track_memory`` of the same owner returns the SAME tracker, so
    multiple instances (two KV pools at one bucket, two trainers) compose
    by deltas.  ``close()`` removes the owner from the ledger outright —
    only sole owners should call it; shared sites ``free`` their own
    bytes instead."""

    __slots__ = ("owner", "category", "bytes", "peak", "allocs", "frees")

    def __init__(self, owner, category):
        self.owner = str(owner)
        self.category = str(category)
        self.bytes = 0
        self.peak = 0
        self.allocs = 0
        self.frees = 0

    def alloc(self, nbytes):
        n = int(nbytes)
        with _mem_lock:
            self.bytes += n
            self.allocs += 1
            if self.bytes > self.peak:
                self.peak = self.bytes
        return self

    def free(self, nbytes):
        with _mem_lock:
            self.bytes -= int(nbytes)
            self.frees += 1
        return self

    def set(self, nbytes):
        with _mem_lock:
            self.bytes = int(nbytes)
            if self.bytes > self.peak:
                self.peak = self.bytes
        return self

    def close(self):
        with _mem_lock:
            self.bytes = 0
            if _mem_owners.get(self.owner) is self:
                del _mem_owners[self.owner]

    def __repr__(self):
        return (f"MemoryTracker({self.owner!r}, {self.category!r}, "
                f"bytes={self.bytes})")


def array_nbytes(x):
    """Device-buffer footprint of an array / NDArray / state tree,
    computed from shape x dtype — THE shared helper every accounting
    site uses (gluon Trainer, executor, predictor).  Deliberately never
    touches the raw buffer: reading ``.nbytes`` off a pending
    bulk-deferred array would force-flush the engine's micro-graph, and
    accounting must never do that.  None and unshaped objects count 0."""
    import numpy as _np

    if x is None:
        return 0
    if isinstance(x, (list, tuple)):
        return sum(array_nbytes(s) for s in x)
    try:
        shape, dtype = x.shape, x.dtype
    except Exception:
        return 0
    n = 1
    for d in shape:
        n *= int(d)
    try:
        return n * _np.dtype(dtype).itemsize
    except Exception:
        return 0


def track_memory(owner, category="other"):
    """Register (or look up) a ledger owner and return its
    :class:`MemoryTracker`.  ``category`` groups owners for the
    per-category rollup (house categories: ``params``,
    ``optimizer_state``, ``kv_cache``, ``infeed``, ``programs``); the
    first registration's category wins."""
    with _mem_lock:
        t = _mem_owners.get(str(owner))
        if t is None:
            t = MemoryTracker(owner, category)
            _mem_owners[str(owner)] = t
        return t


def memory_ledger():
    """Snapshot of the device-memory ledger::

        {"owners": {owner: {category, bytes, peak, allocs, frees}},
         "by_category": {category: bytes}, "total_bytes": int}

    ``dump()`` embeds it under ``otherData.memory.ledger``;
    ``tools/memory_report.py`` renders it."""
    with _mem_lock:
        owners = {o: {"category": t.category, "bytes": t.bytes,
                      "peak": t.peak, "allocs": t.allocs, "frees": t.frees}
                  for o, t in _mem_owners.items()}
    by_cat = {}
    total = 0
    for info in owners.values():
        by_cat[info["category"]] = (by_cat.get(info["category"], 0)
                                    + info["bytes"])
        total += info["bytes"]
    return {"owners": owners, "by_category": by_cat, "total_bytes": total}


def _ledger_categories():
    """Flat ``{category: bytes}`` + ``total`` for the counter track (one
    Perfetto series per category)."""
    with _mem_lock:
        if not _mem_owners:
            return {}
        cats = {}
        total = 0
        for t in _mem_owners.values():
            cats[t.category] = cats.get(t.category, 0) + t.bytes
            total += t.bytes
    cats["total"] = total
    return cats


def memory_postmortems():
    """The postmortem reports emitted so far (bounded FIFO; newest
    last)."""
    with _mem_lock:
        return [dict(r) for r in _mem_postmortems]


# -- OOM forensics -----------------------------------------------------------

_OOM_TOKENS = ("RESOURCE_EXHAUSTED", "Out of memory", "OutOfMemory",
               "out of memory")


def is_resource_exhausted(exc):
    """Whether an exception looks like a device allocation failure (XLA
    surfaces OOM as ``XlaRuntimeError: RESOURCE_EXHAUSTED: Out of memory
    while trying to allocate N bytes``)."""
    if exc is None:
        return False
    if type(exc).__name__ in ("XlaRuntimeError", "MemoryBudgetError"):
        msg = str(exc)
        return any(t in msg for t in _OOM_TOKENS) or "budget" in msg
    msg = str(exc)
    return any(t in msg for t in _OOM_TOKENS)


_ALLOC_RE = None  # compiled lazily (re import off the hot path)


def _parse_failed_bytes(msg):
    """Best-effort size of the failed allocation from an XLA OOM message
    (``... trying to allocate 4294967296 bytes ...`` /
    ``Attempting to reserve 5.81G ...``).  None when unparseable."""
    global _ALLOC_RE
    if _ALLOC_RE is None:
        import re
        _ALLOC_RE = re.compile(
            r"(?:allocat\w+|reserve)\s+([0-9][0-9.]*)\s*"
            r"(bytes?|[KMG]i?B?\b)?", re.IGNORECASE)
    m = _ALLOC_RE.search(msg or "")
    if not m:
        return None
    try:
        val = float(m.group(1))
    except ValueError:
        return None
    unit = (m.group(2) or "bytes").upper()
    mult = {"K": 1 << 10, "M": 1 << 20, "G": 1 << 30}.get(unit[0], 1)
    return int(val * mult)


def _fmt_bytes(n):
    if n is None:
        return "?"
    for unit, div in (("GiB", 1 << 30), ("MiB", 1 << 20), ("KiB", 1 << 10)):
        if abs(n) >= div:
            return f"{n / div:.2f} {unit}"
    return f"{n} B"


def oom_postmortem(where, failed_bytes=None, error=None, kind="oom"):
    """Emit ONE structured device-memory postmortem: the top ledger
    owners by bytes, per-category totals, live device stats, and the
    failed allocation size.  Logged as a single ERROR line, appended to
    :func:`memory_postmortems`, counted in ``memory_oom_postmortem``.
    Returns the report dict."""
    led = memory_ledger()
    top = sorted(led["owners"].items(), key=lambda kv: -kv[1]["bytes"])[:8]
    report = {
        "kind": kind,                  # "oom" | "budget"
        "where": str(where),
        "time_unix": time.time(),
        "step": _step_id,
        "failed_bytes": failed_bytes,
        "error": str(error)[:500] if error is not None else None,
        "device": device_memory_stats(),
        "ledger_total_bytes": led["total_bytes"],
        "by_category": led["by_category"],
        "top_owners": [{"owner": o, **info} for o, info in top],
    }
    with _mem_lock:
        _mem_postmortems.append(report)
        while len(_mem_postmortems) > _MAX_POSTMORTEMS:
            _mem_postmortems.pop(0)
    incr("memory_oom_postmortem")
    owners_line = ", ".join(
        f"{o}={_fmt_bytes(i['bytes'])} ({i['category']})"
        for o, i in top[:4]) or "no registered owners"
    _logger.error(
        "device-memory postmortem at %s: failed to allocate %s "
        "(%s); ledger attributes %s — top owners: %s "
        "[see profiler.memory_postmortems() / tools/memory_report.py]",
        where, _fmt_bytes(failed_bytes), kind,
        _fmt_bytes(led["total_bytes"]), owners_line)
    return report


def maybe_oom_postmortem(exc, where):
    """Choke-point hook: when ``exc`` is a device allocation failure,
    emit exactly ONE postmortem per exception object (the report is
    attached to the exception, so nested choke points — an engine flush
    inside an SPMD step — cannot double-report as it propagates).
    Returns the report, or None for unrelated errors.  Callers re-raise
    the original exception afterwards."""
    if exc is None or not is_resource_exhausted(exc):
        return None
    rep = getattr(exc, "_mx_postmortem", None)
    if rep is not None:
        return rep
    rep = oom_postmortem(where, failed_bytes=_parse_failed_bytes(str(exc)),
                         error=exc)
    try:
        exc._mx_postmortem = rep
    except Exception:
        pass
    return rep


# -- budgeted admission ------------------------------------------------------


class MemoryBudgetError(RuntimeError):
    """An allocation was refused by :meth:`MemoryBudget.check` — the
    budget's postmortem rides on ``._mx_postmortem``."""


class MemoryBudget:
    """The one admission API device-buffer holders consult instead of raw
    ``memory_stats()`` probes.

    Parameters
    ----------
    limit_mb : explicit byte budget (MiB); ``None`` reads
        ``MXNET_MEM_BUDGET_MB`` (0/unset = no explicit cap, only the
        device's own ``bytes_limit`` caps).
    pressure_frac : occupancy fraction treated as pressure
        (``MXNET_MEM_PRESSURE_FRAC``, default 0.95).

    ``usage_bytes()`` is the device's live ``bytes_in_use`` (max across
    local devices) when the backend reports it, else the ledger total —
    so budgets work on CPU tests exactly as on HBM."""

    def __init__(self, limit_mb=None, pressure_frac=None):
        # an explicit limit_mb is pinned; None follows the env DYNAMICALLY
        # (the process singleton is created lazily by whoever probes first
        # — a pipeline tick must not freeze a budget the user exports
        # just before building their server)
        self._limit_mb = limit_mb
        self.pressure_frac = (
            float(pressure_frac) if pressure_frac is not None
            else _env_float("MXNET_MEM_PRESSURE_FRAC", 0.95))

    @property
    def limit_bytes(self):
        mb = self._limit_mb
        if mb is None:
            mb = _env_float("MXNET_MEM_BUDGET_MB", 0.0)
        return int(float(mb) * (1 << 20)) if mb else None

    @staticmethod
    def _usage(stats):
        if stats:
            return max(s["bytes_in_use"] for s in stats.values())
        return memory_ledger()["total_bytes"]

    def usage_bytes(self):
        return self._usage(device_memory_stats())

    def headroom_bytes(self):
        """Bytes left under the explicit limit; None when uncapped."""
        limit = self.limit_bytes
        if limit is None:
            return None
        return limit - self.usage_bytes()

    def would_fit(self, nbytes=0):
        """Whether an ``nbytes`` allocation fits: under the explicit
        limit when one is set, else under every device's own
        ``bytes_limit`` (trivially True when neither exists).  One
        device probe per call — this runs on admission hot paths."""
        n = int(nbytes)
        stats = device_memory_stats()
        limit = self.limit_bytes
        if limit is not None:
            return self._usage(stats) + n <= limit
        for s in stats.values():
            if s["bytes_limit"] and s["bytes_in_use"] + n > s["bytes_limit"]:
                return False
        return True

    def under_pressure(self, frac=None):
        """Whether occupancy exceeds ``frac`` of the capacity (device
        ``bytes_limit`` and/or the explicit budget) — the backoff signal
        the DataPipeline autotuner and GenerationServer admission read.
        One device probe per call."""
        frac = self.pressure_frac if frac is None else float(frac)
        stats = device_memory_stats()
        for s in stats.values():
            if s["bytes_limit"] and s["bytes_in_use"] > frac * s["bytes_limit"]:
                return True
        limit = self.limit_bytes
        if limit is not None:
            return self._usage(stats) > frac * limit
        return False

    def check(self, nbytes, owner="?"):
        """Raise :class:`MemoryBudgetError` (with exactly one postmortem)
        when ``nbytes`` does not fit — the loud variant of
        :meth:`would_fit` for sites that must fail an admission rather
        than defer it."""
        if self.would_fit(nbytes):
            return
        rep = oom_postmortem(f"budget:{owner}", failed_bytes=int(nbytes),
                             kind="budget")
        err = MemoryBudgetError(
            f"memory budget refused {_fmt_bytes(int(nbytes))} for "
            f"{owner!r}: usage {_fmt_bytes(self.usage_bytes())} of "
            f"limit {_fmt_bytes(self.limit_bytes)} "
            f"(MXNET_MEM_BUDGET_MB / MemoryBudget)")
        err._mx_postmortem = rep
        raise err

    def stats(self):
        return {"limit_bytes": self.limit_bytes,
                "pressure_frac": self.pressure_frac,
                "usage_bytes": self.usage_bytes()}


_process_budget = None


def memory_budget():
    """The process-wide :class:`MemoryBudget` (``MXNET_MEM_BUDGET_MB``-
    configured singleton) — what subsystems consult when no explicit
    budget object was handed to them."""
    global _process_budget
    if _process_budget is None:
        _process_budget = MemoryBudget()
    return _process_budget


def _memory_provider():
    """Built-in ``memory`` metrics provider: ledger totals per category,
    owner count, postmortem count and live device occupancy as flat
    gauges (``mxnet_memory_ledger_bytes``, ``mxnet_memory_<cat>_bytes``,
    ...)."""
    led = memory_ledger()
    out = {"ledger_bytes": led["total_bytes"],
           "owners": len(led["owners"])}
    for cat, b in led["by_category"].items():
        out[f"{cat}_bytes"] = b
    with _mem_lock:
        out["postmortems"] = len(_mem_postmortems)
    stats = device_memory_stats()
    if stats:
        out["device_bytes_in_use"] = max(s["bytes_in_use"]
                                         for s in stats.values())
        out["device_bytes_limit"] = max(s["bytes_limit"]
                                        for s in stats.values())
    b = _process_budget
    if b is not None and b.limit_bytes is not None:
        out["budget_limit_bytes"] = b.limit_bytes
    return out


register_metrics_provider("memory", _memory_provider)


# ---------------------------------------------------------------------------
# Compilation observability (ISSUE 10): global compile registry, recompile
# attribution, XLA cost accounting, steady-state compile guard
# ---------------------------------------------------------------------------

# "Compile the program, not the ops" only pays off while programs actually
# stop compiling.  Every jit site in the repo (dispatch cache, engine bulk
# flush, SPMD step, executor/predictor binds, serving warmup, kvstore
# flatten/unflatten, fused optimizer group_apply, hybridized CachedOp)
# reports each compilation here through ONE helper — record_compile() —
# with the full input signature, so the registry can answer "what compiled,
# why, and what did it cost":
#
# * a compile at a site that already holds a signature for the same
#   program is a RECOMPILE: the new signature is diffed against the
#   nearest cached one and the exact offending argument is named (shape
#   drift / dtype flip / new static value / sharding change) in a
#   ``compile.recompile`` span + one structured log line;
# * where the site can hand over a ``jax.stages.Lowered``, XLA's
#   ``cost_analysis()`` (FLOPs / bytes accessed) and ``memory_analysis()``
#   (executable footprint) ride along (``MXNET_COMPILE_COST=1`` lets
#   lazily-jitted sites lower once more just for the accounting);
# * a **steady-state guard** turns "no recompiles after warmup" from a
#   benchmark convention into an enforced property: once armed (by
#   ``serving.InferenceServer.start()`` post-warmup, by ``SPMDTrainer``
#   after its first step, or automatically after
#   ``MXNET_COMPILE_WARMUP_STEPS`` step boundaries), every further compile
#   bumps ``recompile_steady_state``; with ``MXNET_COMPILE_GUARD=warn`` it
#   also logs ONE warning, with ``=raise`` it raises CompileGuardError.
#
# tools/compile_report.py summarizes a dump by site; a ``compile``
# metrics provider feeds per-site stats into metrics_snapshot() ->
# JSONL / Prometheus.  See docs/observability.md#compilation-observability.


class CompileGuardError(RuntimeError):
    """A jit compilation happened while the steady-state compile guard was
    armed and ``MXNET_COMPILE_GUARD=raise`` (a recompilation storm caught
    at its first stall instead of pages of slow-step logs)."""


_compile_lock = _threading.Lock()
_compile_records = []      # bounded FIFO of per-compile record dicts
_compile_sites = {}        # site -> {"count","ms","recompiles","sigs"}
_MAX_COMPILE_RECORDS = _env_int("MXNET_COMPILE_LOG_SIZE", 4096)
_MAX_SITE_SIGS = 128       # per-site LRU of cached signatures to diff against
_site_tls = _threading.local()   # .stack of compile_site() label overrides

_guard = {
    "armed": False,        # record_compile counts steady-state violations
    "armed_by": None,      # "serving" / "spmd.trainer" / "warmup_steps" / ...
    "warned": False,       # warn mode fires exactly once per arming
    "boundaries": 0,       # step boundaries seen toward the warmup auto-arm
    "paused": 0,           # compile_guard_paused() nesting depth
}


def _guard_mode():
    """None (off), "warn" or "raise".  ``set_config(compile_guard=...)``
    wins over MXNET_COMPILE_GUARD: "warn"/"raise" select a mode, any
    OTHER non-None value (``"off"``, ``False``) forces the guard off even
    with the env var exported; ``None`` (the default) defers to the
    env."""
    v = _config.get("compile_guard")
    if v is None:
        v = os.environ.get("MXNET_COMPILE_GUARD") or None
    if v in ("warn", "raise"):
        return v
    return None


def _guard_warmup_steps():
    v = _config.get("compile_warmup_steps")
    if v is None:
        return _env_int("MXNET_COMPILE_WARMUP_STEPS", 32)
    return int(v)


def jit_cache_size(fn):
    """pjit's aval-cache size for a jitted callable — THE exact, O(1)
    did-this-call-compile probe for sites whose one persistent jit
    wrapper is shared across signatures (kvstore flatten, fused
    group_apply): a cache growth across a call IS one compile.  Returns
    -1 when the private ``_cache_size`` API is unavailable, in which case
    callers must skip recording (under-reporting a site beats fabricating
    phantom compiles that could trip a raise-mode guard on a cache
    hit)."""
    try:
        return fn._cache_size()
    except Exception:
        return -1


def compile_cost_enabled():
    """Whether lazily-jitted sites should lower a second time purely for
    XLA cost accounting (``MXNET_COMPILE_COST=1`` /
    ``set_config(compile_cost=True)``).  Off by default: the extra
    ``fn.lower()`` roughly doubles each site's compile wall time."""
    v = _config.get("compile_cost")
    if v is None:
        return os.environ.get("MXNET_COMPILE_COST", "0") == "1"
    return bool(v)


def arm_compile_guard(source="manual"):
    """Arm the steady-state compile guard: from now on every compilation
    reported to the registry counts as a steady-state violation
    (``recompile_steady_state``), and ``MXNET_COMPILE_GUARD=warn|raise``
    escalates.  ``serving.InferenceServer.start()`` arms it after bucket
    warmup; ``SPMDTrainer`` after its first compiled step."""
    with _compile_lock:
        if not _guard["armed"]:
            _guard["armed"] = True
            _guard["armed_by"] = source


def disarm_compile_guard():
    """Disarm the guard and reset its warn-once latch (tests; re-warming a
    model after a deliberate shape change)."""
    with _compile_lock:
        _guard["armed"] = False
        _guard["armed_by"] = None
        _guard["warned"] = False
        _guard["boundaries"] = 0


def compile_guard_state():
    with _compile_lock:
        return {"armed": _guard["armed"], "armed_by": _guard["armed_by"],
                "mode": _guard_mode(), "paused": _guard["paused"] > 0,
                "warmup_steps": _guard_warmup_steps(),
                "boundaries": _guard["boundaries"]}


class compile_guard_paused:
    """``with profiler.compile_guard_paused():`` — compilations inside the
    block are registered but not judged (a declared re-warm phase, e.g.
    rebinding a server for a new bucket ladder)."""

    def __enter__(self):
        with _compile_lock:
            _guard["paused"] += 1
        return self

    def __exit__(self, *a):
        with _compile_lock:
            _guard["paused"] -= 1
        return False


def _guard_tick():
    """Count one step boundary toward the MXNET_COMPILE_WARMUP_STEPS
    auto-arm (runs on every boundary, profiler active or not — the guard
    is independent of tracing)."""
    if _guard["armed"] or _guard_mode() is None:
        return
    with _compile_lock:
        _guard["boundaries"] += 1
        if _guard["boundaries"] >= _guard_warmup_steps():
            _guard["armed"] = True
            _guard["armed_by"] = "warmup_steps"


class compile_site:
    """``with profiler.compile_site('serving.warmup'):`` — nested
    ``record_compile`` calls on this thread report under the given site
    label instead of their own (innermost wins).  The serving tier wraps
    its bucket warmup and its dispatch path so an executor compile is
    attributed to the serving phase that triggered it."""

    __slots__ = ("_label",)

    def __init__(self, label):
        self._label = str(label)

    def __enter__(self):
        st = getattr(_site_tls, "stack", None)
        if st is None:
            st = _site_tls.stack = []
        st.append(self._label)
        return self

    def __exit__(self, *a):
        _site_tls.stack.pop()
        return False


def _active_site(site):
    st = getattr(_site_tls, "stack", None)
    return st[-1] if st else site


# -- signature tokens --------------------------------------------------------
# A compile signature is a flat dict ``{arg_name: token}`` where a token is
# either an array descriptor or a static-value descriptor; the optional
# "__program__" entry namespaces signatures within a site (two different
# ops compiled by the dispatch cache are different programs, not a
# recompile of one another).  Sites build tokens with sig_array/sig_static
# so the diff below can classify drift precisely.


def sig_array(a):
    """Signature token for an array-like argument: shape, dtype, and (for
    mesh-sharded arrays) the partition spec."""
    try:
        tok = {"k": "array", "shape": tuple(int(d) for d in a.shape),
               "dtype": str(a.dtype)}
    except Exception:
        return sig_static(type(a).__name__)
    spec = getattr(getattr(a, "sharding", None), "spec", None)
    if spec is not None:
        tok["sharding"] = str(spec)
    return tok


def sig_static(v):
    """Signature token for a static (baked-into-the-trace) value."""
    return {"k": "static", "value": repr(v)[:120]}


def _tok_str(tok):
    if not isinstance(tok, dict):
        return str(tok)
    if tok.get("k") == "array":
        s = "x".join(str(d) for d in tok.get("shape", ()))
        out = f"{tok.get('dtype', '?')}[{s}]"
        if "sharding" in tok:
            out += f"@{tok['sharding']}"
        return out
    return str(tok.get("value"))


_DRIFT_NAMES = {"shape": "shape drift", "dtype": "dtype flip",
                "static": "new static value", "sharding": "sharding change",
                "kind": "array/static kind change", "added": "new argument",
                "removed": "argument removed"}


def diff_signatures(old, new):
    """Classify what changed between two compile signatures.  Returns a
    list of findings ``{"arg", "kind", "old", "new"}`` where kind is one
    of shape / dtype / sharding / static / kind / added / removed —
    the vocabulary of the recompile attribution line."""
    findings = []
    for name in sorted(set(old) | set(new)):
        if name == "__program__":
            continue
        o, n = old.get(name), new.get(name)
        if o == n:
            continue
        if o is None or n is None:
            findings.append({"arg": name,
                             "kind": "added" if o is None else "removed",
                             "old": _tok_str(o) if o else None,
                             "new": _tok_str(n) if n else None})
            continue
        o = o if isinstance(o, dict) else {"k": "static", "value": str(o)}
        n = n if isinstance(n, dict) else {"k": "static", "value": str(n)}
        if o.get("k") != n.get("k"):
            kind = "kind"
        elif o.get("k") == "array":
            if tuple(o.get("shape", ())) != tuple(n.get("shape", ())):
                kind = "shape"
            elif o.get("dtype") != n.get("dtype"):
                kind = "dtype"
            else:
                kind = "sharding"
        else:
            kind = "static"
        findings.append({"arg": name, "kind": kind,
                         "old": _tok_str(o), "new": _tok_str(n)})
    return findings


def _attribution_line(findings):
    if not findings:
        return "identical signature recompiled (jit cache evicted?)"
    f = findings[0]
    line = (f"argument {f['arg']!r}: {_DRIFT_NAMES.get(f['kind'], f['kind'])}"
            f" {f['old']} -> {f['new']}")
    if len(findings) > 1:
        line += f" (+{len(findings) - 1} more drifted)"
    return line


def _sig_key(signature):
    return repr(sorted(
        (k, sorted(v.items()) if isinstance(v, dict) else v)
        for k, v in signature.items()))


def _sig_similarity(a, b):
    """Field-granular similarity score used to pick the NEAREST cached
    signature a recompile is diffed against: an exact argument match
    scores 4, a partially-matching array token scores 1 per equal
    subfield (shape / dtype / sharding)."""
    score = 0
    for k, av in a.items():
        bv = b.get(k)
        if bv is None:
            continue
        if av == bv:
            score += 4
        elif (isinstance(av, dict) and isinstance(bv, dict)
                and av.get("k") == "array" and bv.get("k") == "array"):
            score += (tuple(av.get("shape", ())) == tuple(bv.get("shape", ())))
            score += (av.get("dtype") == bv.get("dtype"))
            score += (av.get("sharding") == bv.get("sharding"))
    return score


def _extract_cost(lowered):
    """Best-effort XLA cost/memory accounting from a ``Lowered`` (or
    already-``Compiled``) stage.  Returns a flat dict or None; never
    raises (accounting must not take the compiling site down)."""
    try:
        compiled = lowered.compile() if hasattr(lowered, "compile") else lowered
    except Exception:
        return None
    out = {}
    try:
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else {}
        if ca:
            if "flops" in ca:
                out["flops"] = float(ca["flops"])
            if "bytes accessed" in ca:
                out["bytes_accessed"] = float(ca["bytes accessed"])
    except Exception:
        pass
    try:
        ma = compiled.memory_analysis()
        for src, dst in (("temp_size_in_bytes", "temp_bytes"),
                         ("argument_size_in_bytes", "argument_bytes"),
                         ("output_size_in_bytes", "output_bytes"),
                         ("generated_code_size_in_bytes", "code_bytes")):
            v = getattr(ma, src, None)
            if v is not None:
                out[dst] = int(v)
    except Exception:
        pass
    return out or None


def record_compile(site, signature, wall_ms, fn=None, args=None, kwargs=None,
                   lowered=None, text=None):
    """Report one jit compilation into the process-wide compile registry.

    Parameters
    ----------
    site : str — the compiling subsystem (``"ops.dispatch"``,
        ``"spmd.step"``, ...); a surrounding :class:`compile_site` scope
        overrides it.
    signature : dict name -> :func:`sig_array`/:func:`sig_static` token
        (+ optional ``"__program__"`` namespacing distinct programs at one
        site).  THE unit recompile attribution diffs.
    wall_ms : float — wall time of the compiling call (trace + compile +
        first execution for lazily-jitted sites).
    fn, args, kwargs : optional jitted callable + example arguments; when
        :func:`compile_cost_enabled`, the helper lowers once more to
        extract XLA cost/memory analysis.  ``lowered`` short-circuits that
        with a site-provided ``Lowered``/``Compiled`` stage.
    text : optional thunk ``() -> str`` giving the program's optimized HLO
        text on demand (:func:`compiled_text`); it may hold the jitted
        function and its ABSTRACT signature, never device buffers.

    Returns the record dict appended to the registry.  In guard raise
    mode this RAISES CompileGuardError after the bookkeeping — call it
    outside any except-and-fallback block.
    """
    site = _active_site(str(site))
    signature = dict(signature or {})
    program = signature.get("__program__")
    wall_ms = float(wall_ms)
    if text is not None:
        _compile_text_of[site] = text
    if lowered is None and fn is not None and compile_cost_enabled():
        try:
            lowered = fn.lower(*(args or ()), **(kwargs or {}))
        except Exception:
            lowered = None
    cost = _extract_cost(lowered) if lowered is not None else None
    if cost and cost.get("code_bytes"):
        # compiled-executable footprint rides the PR 9 memory_analysis
        # into the ledger: programs own bytes too (opt-in with the cost
        # accounting itself).  CUMULATIVE by design — executables live in
        # process-wide jit caches whose evictions are invisible from
        # here, so this owner is an upper bound on resident code, not an
        # exact balance like the buffer owners.
        track_memory("compiled_programs", "programs").alloc(
            cost["code_bytes"])

    key = _sig_key(signature)
    now = _perf()
    with _compile_lock:
        ent = _compile_sites.setdefault(
            site, {"count": 0, "ms": 0.0, "recompiles": 0,
                   "sigs": _OrderedDict()})
        sigs = ent["sigs"]
        recompile = False
        findings = []
        if key in sigs:
            # the site compiled a signature it had already compiled: its
            # own cache (or jax's) dropped the entry — still a recompile
            recompile = True
            sigs.move_to_end(key)
        else:
            peers = [s for s in sigs.values()
                     if s.get("__program__") == program]
            if peers:
                recompile = True
                # nearest cached signature at FIELD granularity (a dtype
                # flip should diff against the same-shape signature, not
                # whichever was cached first); newest wins ties
                nearest = max(reversed(peers),
                              key=lambda s: _sig_similarity(s, signature))
                findings = diff_signatures(nearest, signature)
            sigs[key] = signature
            while len(sigs) > _MAX_SITE_SIGS:
                sigs.popitem(last=False)
        ent["count"] += 1
        ent["ms"] += wall_ms
        if recompile:
            ent["recompiles"] += 1
        armed = _guard["armed"] and _guard["paused"] == 0
        attribution = _attribution_line(findings) if recompile else None
        rec = {"site": site, "program": program, "signature": signature,
               "wall_ms": round(wall_ms, 3), "step": _step_id,
               "time_unix": time.time(), "recompile": recompile,
               "attribution": attribution, "findings": findings,
               "steady_state": armed, "cost": cost}
        _compile_records.append(rec)
        while len(_compile_records) > _MAX_COMPILE_RECORDS:
            _compile_records.pop(0)
    incr("compile_total")
    incr("compile_ms_total", int(round(wall_ms)))
    if armed:
        incr("recompile_steady_state")
    if _active:
        t0 = now - wall_ms / 1e3
        record_span("compile.jit", "compile", t0, now,
                    args={"site": site, "wall_ms": round(wall_ms, 3),
                          "program": program})
        if recompile:
            record_span("compile.recompile", "compile", now, now,
                        args={"site": site, "attribution": attribution})
    if recompile:
        # THE attribution line: one structured log naming the exact
        # offending argument, whatever the guard mode
        _logger.info("recompile at %s%s: %s (wall %.1f ms, step %d)",
                     site, f" [{program}]" if program else "", attribution,
                     wall_ms, rec["step"])
    if armed:
        mode = _guard_mode()
        if mode == "raise":
            raise CompileGuardError(
                f"steady-state compile guard (armed by "
                f"{_guard['armed_by']}): {site} compiled "
                f"{'— ' + attribution if attribution else 'a new program'} "
                f"after warmup (wall {wall_ms:.1f} ms)")
        if mode == "warn":
            with _compile_lock:
                first = not _guard["warned"]
                _guard["warned"] = True
            if first:
                _logger.warning(
                    "steady-state compile guard (armed by %s): %s compiled "
                    "after warmup%s (wall %.1f ms) — further violations "
                    "count in recompile_steady_state without logging",
                    _guard["armed_by"], site,
                    f" — {attribution}" if attribution else "", wall_ms)
    return rec


def compile_registry():
    """Snapshot of the compile registry: ``{"sites": {site: {count, ms,
    recompiles, signatures}}, "records": [...]}`` — what ``dump()`` embeds
    under ``otherData.compiles`` and ``tools/compile_report.py`` reads."""
    with _compile_lock:
        sites = {s: {"count": e["count"], "ms": round(e["ms"], 3),
                     "recompiles": e["recompiles"],
                     "signatures": len(e["sigs"])}
                 for s, e in _compile_sites.items()}
        records = [dict(r) for r in _compile_records]
    return {"sites": sites, "records": records}


_compile_text_of = {}   # site -> thunk of its newest program's HLO text


def compiled_text(site):
    """Optimized HLO text of the newest program ``site`` reported with a
    ``text=`` thunk (``spmd.step`` does), else None.  Computed when asked
    for: an ahead-of-time compile of the same function over the same
    abstract signature — a load where the persistent compile cache is on.
    What joins a device trace's ``%fusion.7`` to its ``op_name`` scope."""
    thunk = _compile_text_of.get(site)
    return None if thunk is None else thunk()


def compile_stats():
    """Per-site compile summary only (no per-record detail)."""
    return compile_registry()["sites"]


def reset_compiles():
    """Drop every compile record and cached signature (tests; a fresh
    measurement window).  Guard state is separate — see
    :func:`disarm_compile_guard`."""
    with _compile_lock:
        _compile_records.clear()
        _compile_sites.clear()
        _compile_text_of.clear()


def _compile_provider():
    """Built-in ``compile`` metrics provider: per-site compile counts and
    wall totals as flat gauges (``mxnet_compile_<site>_total`` etc.)."""
    out = {}
    with _compile_lock:
        total = ms = rec = 0
        for site, e in _compile_sites.items():
            k = site.replace(".", "_")
            out[f"{k}_total"] = e["count"]
            out[f"{k}_ms"] = round(e["ms"], 3)
            out[f"{k}_recompiles"] = e["recompiles"]
            total += e["count"]
            ms += e["ms"]
            rec += e["recompiles"]
    out["total"] = total
    out["ms_total"] = round(ms, 3)
    out["recompiles"] = rec
    out["guard_armed"] = 1 if _guard["armed"] else 0
    return out


register_metrics_provider("compile", _compile_provider)


# ---------------------------------------------------------------------------
# Control surface
# ---------------------------------------------------------------------------


def set_config(**kwargs):
    """Parity: ``mx.profiler.set_config`` — unknown keys are accepted and
    ignored (the reference has many backend-specific flags).  Meaningful
    keys here: ``filename``, ``ring_size``, ``slow_step_ms``,
    ``slow_step_auto``, ``slow_step_auto_mult``, ``step_window``,
    ``memory_sampling``, plus the compile-observability knobs
    ``compile_guard`` ("warn"/"raise"/None — overrides
    MXNET_COMPILE_GUARD), ``compile_warmup_steps`` and ``compile_cost``
    (overrides MXNET_COMPILE_COST).  ``ring_size`` takes effect at the
    NEXT ``start()`` — live rings keep the capacity they were built
    with."""
    global _telemetry, _active, _step_t0
    _config.update(kwargs)
    if "slow_step_ms" in kwargs:
        was_active = _active
        _telemetry = (kwargs["slow_step_ms"] is not None
                      or os.environ.get("MXNET_PROFILER_SLOW_STEP_MS")
                      is not None)
        _active = _recording or _telemetry
        if _active and not was_active:
            # re-anchor: the stale _step_t0 from before the disabled gap
            # would bill the whole gap to the next step (stop() resets it
            # for the same reason)
            _step_t0 = None
            _goodput_open()
        elif was_active and not _active:
            _goodput_close()


def state():
    return "running" if _state["running"] else "stopped"


_trace_warned = False


def _trace_error(what, exc):
    """Satellite 3: a broken xprof install must be diagnosable — warn once
    per process and always count, instead of a silent ``except: pass``."""
    global _trace_warned
    incr("profiler_trace_error")
    if not _trace_warned:
        _trace_warned = True
        _warnings.warn(
            f"jax.profiler.{what} failed ({type(exc).__name__}: {exc}); "
            "device-side xprof tracing is unavailable for this run — the "
            "python span recorder still captures host-side spans. "
            "(warned once; see the profiler_trace_error counter)",
            RuntimeWarning, stacklevel=3)


def _arm(fresh):
    """Shared start/resume body: start the xprof trace and arm the span
    recorder.  ``fresh`` discards prior spans/telemetry (a new session);
    resume keeps them (the reference's pause/resume accumulates)."""
    global _recording, _active, _ring_gen, _step_t0, _step_thread, _armed_at
    logdir = os.path.dirname(os.path.abspath(_config["filename"])) or "."
    trace_dir = os.path.join(logdir, "mxtpu_profile")
    os.makedirs(trace_dir, exist_ok=True)
    try:
        jax.profiler.start_trace(trace_dir)
        _state["xprof"] = True
    except Exception as e:  # unsupported backend / second trace: recorder
        _state["xprof"] = False  # still arms, but the failure is visible
        _trace_error("start_trace", e)
    with _counter_lock:
        # the bucket sums always restart with the step clock: a pause()
        # mid-step leaves a partial step's sums behind, and billing them
        # against a wall clock measured from resume() would corrupt the
        # first post-resume step's split
        _step_acc["host"] = 0.0
        _step_acc["comms"] = 0.0
        if fresh:
            _ring_gen += 1    # abandon previous-generation rings
            _rings.clear()
            _evicted[0] = _evicted[1] = 0
            # fresh telemetry per recording session: a stale rolling window
            # would skew the slow-step percentile baseline
            _step_window.clear()
            _mem_watermark.clear()
            _mem_samples.clear()
    _armed_at = _step_t0 = _perf()
    _step_thread = _threading.get_ident()
    _recording = True
    _active = True
    # the RUN-scoped goodput ledger only opens its wall window here —
    # start() discards spans but never the run's ledger (reset_goodput()
    # is the explicit reset)
    _goodput_open(_armed_at)
    _state.update(running=True, dir=trace_dir, t0=time.perf_counter())


def start():
    """Start a FRESH recording session: arm the span recorder (discarding
    any previously recorded spans/telemetry) and start an xprof trace.
    Trace directory = dirname(filename) (the chrome-trace single file of
    the reference maps onto xprof's directory layout; load it with
    TensorBoard or xprof)."""
    if _state["running"]:
        return
    _arm(fresh=True)


def resume():
    """Re-arm after ``pause()`` WITHOUT discarding what was recorded
    before it — pause/resume accumulates into one trace (reference
    semantics); ``start()`` is the fresh-session entry."""
    if _state["running"]:
        return
    _arm(fresh=False)


def stop():
    """Disarm the span recorder and stop the xprof trace.  Recorded spans
    survive for ``dump()``."""
    global _recording, _active, _step_t0
    if not _state["running"]:
        return
    if _state["xprof"]:
        try:
            jax.profiler.stop_trace()
        except Exception as e:
            _trace_error("stop_trace", e)
        _state["xprof"] = False
    _recording = False
    _active = _telemetry
    # a later telemetry-only step_boundary must anchor fresh, not measure
    # the wall-clock gap since this session's last boundary
    _step_t0 = None
    if not _active:
        # goodput wall stops integrating while nothing observes: a paused
        # profiler billing the pause to "compute" would inflate goodput
        _goodput_close()
    _state["running"] = False


pause = stop  # stop keeps recorded spans, so pause/resume accumulates


# ---------------------------------------------------------------------------
# Chrome-trace serialization
# ---------------------------------------------------------------------------


def _trace_events():
    """All recorded spans as chrome-trace B/E event dicts, ordered so B/E
    pairs nest validly per thread (ties: E before B; outer B before inner
    B; inner E before outer E)."""
    pid = os.getpid()
    with _counter_lock:
        rings = list(_rings)
    keyed = []
    for r in rings:
        for ev in r.snapshot():
            if ev is None:
                continue
            name, cat, t0, t1, step, args = ev
            ts = (t0 - _EPOCH) * 1e6
            te = (t1 - _EPOCH) * 1e6
            if te <= ts:
                te = ts + 0.001  # zero-dur spans still pair B < E
            dur_us = te - ts
            a = {"step": step}
            if args:
                a.update(args)
            keyed.append(((ts, 1, -dur_us),
                          {"ph": "B", "name": name, "cat": cat, "ts": ts,
                           "pid": pid, "tid": r.tid, "args": a}))
            keyed.append(((te, 0, dur_us),
                          {"ph": "E", "name": name, "cat": cat, "ts": te,
                           "pid": pid, "tid": r.tid}))
    keyed.sort(key=lambda kv: kv[0])
    events = [{"ph": "M", "pid": pid, "name": "process_name",
               "args": {"name": f"rank {_proc['rank']} ({_proc['host']})"}}]
    events.extend({"ph": "M", "pid": pid, "tid": r.tid, "name": "thread_name",
                   "args": {"name": r.tname}} for r in rings)
    events.extend(e for _, e in keyed)
    # memory counter track: chrome-trace "C" events Perfetto renders as a
    # per-device bytes_in_use timeline plus one ledger series per category
    with _counter_lock:
        samples = list(_mem_samples)
    for t, dev_use, cats in samples:
        ts = (t - _EPOCH) * 1e6
        for dev, b in dev_use.items():
            events.append({"ph": "C", "name": f"memory {dev}", "pid": pid,
                           "ts": ts, "args": {"bytes_in_use": b}})
        if cats:
            events.append({"ph": "C", "name": "memory ledger", "pid": pid,
                           "ts": ts, "args": dict(cats)})
    return events


def dump(finished=True, profile_process="worker"):
    """Serialize the recorded spans to chrome://tracing JSON at
    ``_config['filename']`` (parity: ``mx.profiler.dump`` writing the
    reference's chrome-trace file).  ``finished=False`` keeps the recorder
    armed (periodic mid-run dumps); the default also ``stop()``s.
    With ``MXNET_PROFILER_TRACE_GZ=1`` the file is gzip-compressed (a
    ``.gz`` suffix is appended unless already present — pod-scale traces
    shrink ~10x and ``tools/trace_report.py``/``trace_merge.py`` read
    them directly).  Returns the path written."""
    path = _config["filename"]
    gz = os.environ.get("MXNET_PROFILER_TRACE_GZ", "0") == "1"
    if gz and not path.endswith(".gz"):
        path += ".gz"
    parent = os.path.dirname(os.path.abspath(path))
    os.makedirs(parent, exist_ok=True)  # telemetry-only sessions never ran
    payload = {                         # _arm()'s makedirs
        "traceEvents": _trace_events(),
        "displayTimeUnit": "ms",
        "otherData": {
            # process identity + wall-clock anchor + offset estimate: what
            # tools/trace_merge.py needs to fuse per-rank dumps into one
            # offset-corrected timeline
            "process": process_info(),
            "counters": counters(),
            "steps": step_stats(),
            "memory_watermark_bytes": memory_watermark(),
            "memory": {
                "ledger": memory_ledger(),
                "postmortems": memory_postmortems(),
                "budget": (memory_budget().stats()
                           if _process_budget is not None
                           or os.environ.get("MXNET_MEM_BUDGET_MB")
                           else None),
            },
            "recorder": recorder_stats(),
            "goodput": goodput_snapshot(),
            "compiles": compile_registry(),
            "compile_guard": compile_guard_state(),
            "xprof_dir": _state["dir"],
        },
    }
    opener = (lambda p: _gzip.open(p, "wt")) if gz else (lambda p: open(p, "w"))
    with opener(path) as f:
        json.dump(payload, f)
    if finished:
        stop()
    return path


def iter_xplane_ops(trace_dir):
    """Yield ``(full_hlo_text, duration_ps)`` for every event on a device
    plane's "XLA Ops" line in the newest ``.xplane.pb`` under ``trace_dir``
    (the "Async XLA Ops" line is skipped — its spans overlap compute).
    Single shared xplane reader — tools/parse_xplane.py and
    tools/trace_report.py present the same stream differently.  Yields
    nothing when no trace/proto reader exists."""
    import glob

    try:
        from tensorflow.tsl.profiler.protobuf import xplane_pb2  # type: ignore
    except Exception:
        return
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    if not paths:
        return
    xs = xplane_pb2.XSpace()
    try:
        with open(max(paths, key=os.path.getmtime), "rb") as f:
            xs.ParseFromString(f.read())
    except Exception:
        return
    for plane in xs.planes:
        if "/device:" not in plane.name:
            continue
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            for ev in line.events:
                yield plane.event_metadata[ev.metadata_id].name, ev.duration_ps


def collapse_hlo_name(text):
    """Reduce a full HLO instruction line to its instance-collapsed
    instruction name (``%fusion.42 = … fusion(…)`` → ``fusion``) and, when
    parseable, the opcode.  Single shared rule for the ``dumps()`` table
    and tools/parse_xplane.py so op attribution cannot drift between them.
    Returns (instruction_name, opcode_or_None)."""
    import re

    m = re.search(r"%([\w\-\.]+) = [^ ]+ ([\w\-]+)\(", text)
    if m:
        inst, opcode = m.groups()
    else:
        m2 = re.search(r"%([\w\-\.]+) = ", text)
        inst = m2.group(1) if m2 else text.split(" ")[0].lstrip("%")
        opcode = None
    return re.sub(r"\.[0-9]+$", "", inst), opcode


def _device_op_stats(trace_dir, topn=40):
    """Aggregate per-HLO-op device time from the xprof trace directory —
    the TPU analog of the reference's per-op aggregate table
    ([U:src/profiler/aggregate_stats.cc]).  Returns [(name, count, total_s)]
    sorted by total time, or [] when no device plane was captured."""
    from collections import defaultdict

    agg = defaultdict(lambda: [0, 0])
    for name, ps in iter_xplane_ops(trace_dir):
        inst, _ = collapse_hlo_name(name)
        a = agg[inst]
        a[0] += 1
        a[1] += ps
    rows = [(k, c, ps / 1e12) for k, (c, ps) in agg.items()]
    rows.sort(key=lambda r: -r[2])
    return rows[:topn]


def dumps(reset=False):
    """Aggregate stats string (parity: ``mx.profiler.dumps``): python-side
    marker table, dispatch counters, step telemetry, plus the per-device-op
    aggregate parsed from the captured xprof trace (run between
    ``start()``/``stop()`` to populate it)."""
    with _counter_lock:
        agg_rows = sorted(((k, v[0], v[1]) for k, v in _agg.items()),
                          key=lambda r: -r[2])
    lines = ["Profile Statistics (python markers):",
             f"{'Name':<40}{'Count':>8}{'Total(ms)':>12}{'Avg(ms)':>12}"]
    for name, cnt, tot in agg_rows:
        lines.append(f"{name:<40}{cnt:>8}{tot * 1e3:>12.3f}{tot / cnt * 1e3:>12.3f}")
    snap = counters()
    labels = counter_labels()
    if any(snap.values()):
        lines.append("")
        lines.append("Dispatch counters:")
        for name, v in sorted(snap.items()):
            lines.append(f"{name:<40}{v:>8}")
            for lab, n in sorted(labels.get(name, {}).items()):
                row = f'  {name}{{reason="{lab}"}}'
                lines.append(f"{row:<40}{n:>8}")
    steps = step_stats()
    if steps:
        lines.append("")
        lines.append("Step telemetry (rolling window):")
        lines.append(f"{'Step':>6}{'Wall(ms)':>12}{'Host(ms)':>12}"
                     f"{'Comms(ms)':>12}{'Device(ms)':>12}")
        for s in steps[-20:]:
            lines.append(f"{s['step']:>6}{s['wall_ms']:>12.3f}"
                         f"{s['host_ms']:>12.3f}{s['comms_ms']:>12.3f}"
                         f"{s['device_ms']:>12.3f}")
    wm = memory_watermark()
    if wm:
        lines.append("")
        lines.append("Device memory watermark (bytes_in_use peak):")
        for dev, b in sorted(wm.items()):
            lines.append(f"{dev:<40}{b:>16}")
    led = memory_ledger()
    if led["owners"]:
        lines.append("")
        lines.append("Device memory ledger (see tools/memory_report.py):")
        lines.append(f"{'Owner':<36}{'Category':<18}{'Bytes':>14}"
                     f"{'Peak':>14}")
        for o, i in sorted(led["owners"].items(),
                           key=lambda kv: -kv[1]["bytes"]):
            lines.append(f"{o:<36}{i['category']:<18}{i['bytes']:>14}"
                         f"{i['peak']:>14}")
        lines.append(f"{'TOTAL':<36}{'':<18}{led['total_bytes']:>14}")
    gp = goodput_snapshot()
    if gp["wall_s"] > 0:
        lines.append("")
        lines.append(f"Goodput ledger: wall {gp['wall_s']:.3f} s, "
                     f"goodput {gp['goodput'] * 100:.1f}%"
                     + ("".join(f", {k} {v:.3f} s"
                                for k, v in gp["top_overhead"])))
    csites = compile_stats()
    if csites:
        lines.append("")
        lines.append("Compilations (per jit site; see compile_report.py):")
        lines.append(f"{'Site':<28}{'Count':>8}{'Total(ms)':>12}"
                     f"{'Recompiles':>12}")
        for s, e in sorted(csites.items(), key=lambda kv: -kv[1]["ms"]):
            lines.append(f"{s:<28}{e['count']:>8}{e['ms']:>12.1f}"
                         f"{e['recompiles']:>12}")
    if _state["dir"]:
        dev = _device_op_stats(_state["dir"])
        if dev:
            lines.append("")
            lines.append(f"Device ops ({_state['dir']}):")
            lines.append(f"{'HLO op':<56}{'Count':>8}{'Total(ms)':>12}")
            for name, cnt, tot in dev:
                lines.append(f"{name[:56]:<56}{cnt:>8}{tot * 1e3:>12.3f}")
        else:
            lines.append(f"(no device-op detail captured; trace dir: {_state['dir']})")
    if reset:
        with _counter_lock:
            # a reset must cover EVERYTHING this dump shows — otherwise
            # per-interval dumps mix fresh marker stats with cumulative
            # counter/step-telemetry/watermark numbers
            _agg.clear()
            _step_window.clear()
            _mem_watermark.clear()
            _mem_samples.clear()
        with _mem_lock:
            # postmortems are EVENTS (reset like counters); the ledger is
            # live buffers and survives — those bytes are still allocated
            _mem_postmortems.clear()
        reset_counters()
        reset_compiles()
    return "\n".join(lines)


class scope(span):
    """``with profiler.scope('fwd'):`` — a :class:`span` of the ``user``
    category (so: in the xprof trace, and in the chrome trace when the
    recorder is armed) that is also tallied in ``dumps()``."""

    __slots__ = ()

    def __init__(self, name="<unk>"):
        span.__init__(self, name, "user")

    def __exit__(self, *a):
        _tally(self._name, _perf() - self._t0)
        return span.__exit__(self, *a)


class Marker:
    """Instant marker (parity: ``profiler.Marker(...).mark()``)."""

    def __init__(self, name, scope_name="process"):
        self._name = name

    def mark(self, scope_name="process"):
        _tally(self._name, 0.0)
        if _recording:
            t = _perf()
            record_span(self._name, "marker", t, t)


if os.environ.get("MXNET_PROFILER_AUTOSTART", "0") == "1":
    start()
    atexit.register(dump)

if (os.environ.get("MXNET_METRICS_PORT", "0") not in ("", "0")
        or os.environ.get("MXNET_METRICS_JSONL")):
    start_metrics()  # env-driven surfaces come up with the process
