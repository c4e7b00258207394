"""One compiled program per training step — the Gluon step fold.

A classic Gluon training step is several host dispatches: the hybridized
forward (CachedOp jit), the autograd backward (one jitted vjp per tape
node), the bucketed ``allreduce_grads`` pushpulls, and one fused
``group_apply`` per optimizer group.  ``SPMDTrainer`` has lowered its whole
step to ONE donated-buffer program since PR 3 — this module brings the same
whole-program compilation to the imperative ``gluon.Trainer`` contract
(the Julia-to-TPU full-compilation result in PAPERS.md: XLA's fusion pays
off at program granularity, not op granularity):

* :class:`StepProgram` (``Trainer.fold_step(loss_fn)``) traces Block
  forward + loss + backward + the fused optimizer tail into one jitted,
  donated-buffer program per (batch signature, optimizer-group-set).  The
  capture enters the SAME ``gluon.block.trace_scope`` ceremony as the
  CachedOp build and the SPMDTrainer step builders (the unification of the
  repo's partial graph capturers), and the optimizer tail composes the
  SAME per-tensor step adapters ``optimizer/fused.py`` groups with
  (``plan_groups``), so folded numerics cannot drift from the unfused
  kernels they inline.  Weights, optimizer state (and under error
  feedback, compression residuals) are donated; the fresh outputs are
  swapped back into the live ``Parameter``/state NDArrays, so folded and
  unfused steps stay interchangeable mid-training and
  ``save_states``/``load_states`` keep working.

* Multi-process runs against a ``dist_sync`` store fold the gradient
  exchange IN-PROGRAM: forward/backward runs per worker shard inside one
  ``shard_map`` over the kvstore's worker mesh, and each size-capped
  gradient bucket becomes an explicit ``psum`` (or the PR 14 codec's
  quantize → integer psum → dequantize, ``comm.traced_allreduce``) graph
  node that depends only on its own bucket's grads — XLA's scheduler is
  free to start a bucket's collective while the remaining backward still
  computes, which is where MLPerf-on-TPU-pods finds most pod-scale
  headroom.

* :func:`fold_update` is the ``MXNET_STEP_FOLD=1`` fast path inside
  ``Trainer.step``: the whole optimizer tail — every fused group — folds
  into ONE donated jitted dispatch instead of one ``group_apply`` per
  group (forward/backward already ran eagerly by the time ``step()`` is
  called, so this is the part of the step ``Trainer.step`` can fold).

* The K-step fold (``Trainer.fold_steps(loss_fn, k)``, K from
  ``MXNET_STEP_FOLD_K``) wraps the SAME per-step body in a ``lax.scan``
  over K pre-staged batches: params, optimizer state (and under error
  feedback, compression residuals) ride the loop carry, per-step
  lr/wd/t and PRNG keys ride as stacked ``[K]`` device arrays, and the
  K per-step losses accumulate in-program — host dispatch cost drops to
  1/K with numerics exactly equal to K unfolded steps.  The input side
  folds too: ``pipeline.stage_window(k)`` hands the program a
  device-resident ``[K, ...]`` stacked batch window the transfer thread
  built ahead of the scan.  ``K=1`` IS the PR 15 program (same site,
  same signature).  Checkpoints land on K boundaries only
  (``save_states`` refuses mid-window; the window cursor rides the
  snapshot payload).  Compile sites ``gluon.step_fold_k`` and (for
  :class:`EvalProgram`, ``Trainer.fold_eval``) ``gluon.fold_eval``.

Escape hatches (docs/step_fold.md): ``MXNET_STEP_FOLD=0`` disables both
entries, a block opts out with ``block._step_fold_opt_out = True``, and
any capture failure or unsupported optimizer falls back to the eager
record/backward/step path (counted in ``step_fold_fallback``), never
erroring.  ``NaiveEngine`` bypasses folding entirely.
"""
from __future__ import annotations

import os as _os
import warnings as _warnings
from time import perf_counter as _perf

import jax
import jax.numpy as jnp
import numpy as _np

from .. import autograd
from .. import engine as _engine
from .. import profiler as _profiler
from ..ndarray.ndarray import NDArray
from ..optimizer import fused as _fused
from ..optimizer.optimizer import _swap
from ..random import get_key
from .block import trace_scope

__all__ = ["StepProgram", "EvalProgram", "fold_update", "fold_enabled",
           "step_fast_path", "fold_k", "host_dispatch_total",
           "DISPATCH_COUNTERS", "FALLBACK_LABELS"]


def fold_enabled():
    """Whether ``Trainer.fold_step`` folds (default yes;
    ``MXNET_STEP_FOLD=0`` is the escape hatch — the returned StepProgram
    still works, running the eager record/backward/step path)."""
    return _os.environ.get("MXNET_STEP_FOLD", "1") != "0"


def fold_k(default=1):
    """The configured fold width K (``MXNET_STEP_FOLD_K``, default 1):
    how many logical training steps ``Trainer.fold_steps`` /
    ``Trainer.fold_eval`` fold into one compiled dispatch when the
    caller does not pass ``k`` explicitly.  K=1 reduces exactly to the
    single-step fold."""
    try:
        k = int(_os.environ.get("MXNET_STEP_FOLD_K", "") or default)
    except ValueError:
        k = default
    return max(1, k)


# Canonical per-reason labels for the ``step_fold_fallback`` counter
# (``profiler.incr_labeled`` — surfaced in ``dumps()``, the metrics
# snapshot and the Prometheus counters): one scrape says WHY a fold ran
# eager, not just how often.
FALLBACK_LABELS = ("env-off", "naive-engine", "block-opt-out",
                   "grad-req-add", "unsupported-optimizer", "async-PS",
                   "capture-failure", "deferred-init")


def step_fast_path():
    """Whether ``Trainer.step`` routes its optimizer tail through
    :func:`fold_update` (opt-in: ``MXNET_STEP_FOLD=1`` exactly — the
    default keeps the established per-group ``group_apply`` path)."""
    return _os.environ.get("MXNET_STEP_FOLD") == "1"


# Counters that each tick once per HOST-ISSUED device dispatch.  The
# steady-state folded step must move this total by exactly 1 (its own
# ``step_fold_call``) — the opperf harness and tests assert the delta.
DISPATCH_COUNTERS = (
    "dispatch_cache_hit", "dispatch_cache_miss", "dispatch_cache_bypass",
    "dispatch_cache_fallback", "bulk_flush", "fused_step_call",
    "allreduce_bucket", "step_fold_call", "fold_eval_call",
)


def host_dispatch_total(counters=None):
    """Sum of the per-dispatch counters (see ``DISPATCH_COUNTERS``)."""
    c = counters if counters is not None else _profiler.counters()
    return sum(c[k] for k in DISPATCH_COUNTERS)


# concrete jax array of an NDArray, flushing a pending bulk deferred in
# place — THE shared flush-before-donation rule (optimizer/fused.py)
_raw = _fused._concrete


def _opted_out(block):
    """Per-block opt-out: ``block._step_fold_opt_out = True`` anywhere in
    the tree keeps the fold off (docs/step_fold.md)."""
    if block is None:
        return False
    if getattr(block, "_step_fold_opt_out", False):
        return True
    return any(_opted_out(c) for c in getattr(block, "_children", {}).values())


class StepProgram:
    """The folded training step for one ``(Trainer, loss_fn)`` pair.

    ``loss_fn(*batch_ndarrays) -> loss NDArray`` computes the loss from
    the batch (calling the Block(s) whose Parameters the Trainer owns);
    calling the program runs forward + backward + allreduce + optimizer
    update as ONE compiled dispatch and returns the loss NDArray.

    Built via ``Trainer.fold_step(loss_fn)``; see docs/step_fold.md for
    the capture contract (what may run inside ``loss_fn``) and the escape
    hatches.
    """

    def __init__(self, trainer, loss_fn, block=None, keep_grads=False,
                 k=None, donate_window=False):
        self._trainer = trainer
        self._loss_fn = loss_fn
        self._block = block
        self._keep_grads = bool(keep_grads)
        self._k = max(1, int(k if k is not None else fold_k()))
        self._donate_window = bool(donate_window)
        self._cache = {}            # (batch sig, group sig, ...) -> entry
        self._fallback_reason = None
        self._fallback_label = None
        self._warned = False
        self._guard_armed = False
        self._dist = None           # _DistRegisters when folding over a mesh
        self._logical_steps = 0     # logical training steps run (any path)
        self._window_pos = 0        # steps since the last window boundary:
                                    # always 0 for K=1 and after any whole-
                                    # window dispatch; step_one moves it —
                                    # save_states refuses while it is != 0
        if not fold_enabled():
            self._fallback_reason = "MXNET_STEP_FOLD=0"
            self._fallback_label = "env-off"
        elif _engine.is_naive():
            self._fallback_reason = "NaiveEngine"
            self._fallback_label = "naive-engine"
        elif _opted_out(block):
            self._fallback_reason = "block opt-out (_step_fold_opt_out)"
            self._fallback_label = "block-opt-out"

    # -- public surface --------------------------------------------------
    @property
    def folded(self):
        """False once the program has fallen back to the eager path for
        good (reason in ``fallback_reason``)."""
        return self._fallback_reason is None

    @property
    def fallback_reason(self):
        return self._fallback_reason

    @property
    def k(self):
        """Configured fold width: logical steps per full window."""
        return self._k

    @property
    def logical_steps(self):
        """Logical training steps this program has run (folded or eager)."""
        return self._logical_steps

    @property
    def window_pos(self):
        """Logical steps since the last window boundary (``0 <= pos < k``).
        Whole-window dispatches — full or epoch-tail — always land back on
        a boundary; only the ``step_one`` escape moves the cursor.  The
        K-boundary checkpoint rule: ``Trainer.save_states`` refuses while
        this is non-zero (docs/step_fold.md#multi-step-fold)."""
        return self._window_pos

    def __call__(self, *batch, batch_size=None):
        tr = self._trainer
        if not tr._kv_initialized:
            tr._init_kvstore()
        nds = [b if isinstance(b, NDArray) else NDArray(jnp.asarray(b))
               for b in batch]
        if self._k > 1:
            return self._window_call(nds, batch_size)
        if batch_size is None:
            batch_size = nds[0].shape[0]
        out = self._call_one(nds, batch_size)
        self._logical_steps += 1
        return out

    def _call_one(self, nds, batch_size):
        tr = self._trainer
        if self._fallback_reason is not None:
            return self._eager_step(nds, batch_size)
        # deferred-init params can only materialize through a real eager
        # forward — run ONE unfused step, then fold from the next call
        # (mirrors HybridBlock.__call__'s DeferredInit retry)
        if any(p._deferred_init is not None or p._data is None
               for p in tr._params):
            return self._eager_step(nds, batch_size)
        # the folded program embeds the gradient collectives — arm the
        # collective watchdog around the whole dispatch (import at call
        # time: gluon must not import the parallel package at load)
        from ..parallel import elastic as _elastic
        _elastic.watchdog_arm("step_fold.call")
        try:
            return self._folded_step(nds, batch_size)
        finally:
            _elastic.watchdog_disarm()

    def _window_call(self, nds, batch_size):
        """One K-window dispatch: ``nds`` are ``[k_window, batch, ...]``
        stacked arrays (``pipeline.stage_window(k)`` hands them over
        device-resident; an epoch tail may carry ``k_window < k``).  Any
        whole-window dispatch lands the program back on a window
        boundary.  Returns the ``[k_window, ...]`` per-step losses."""
        tr = self._trainer
        if nds[0].ndim < 2:
            raise ValueError(
                f"fold_steps(k={self._k}) expects stacked [k, batch, ...] "
                "windows (pipeline.stage_window(k)); got shape "
                f"{tuple(nds[0].shape)} — use step_one() for a single "
                "unstacked batch")
        kw = int(nds[0].shape[0])
        if any(int(nd.shape[0]) != kw for nd in nds):
            raise ValueError(
                "window leading dims disagree: "
                f"{[tuple(nd.shape) for nd in nds]}")
        if batch_size is None:
            batch_size = nds[0].shape[1]
        if self._fallback_reason is not None or any(
                p._deferred_init is not None or p._data is None
                for p in tr._params):
            out = self._eager_window(nds, batch_size)
        else:
            from ..parallel import elastic as _elastic
            _elastic.watchdog_arm("step_fold.call")
            try:
                out = self._folded_step(nds, batch_size, k_window=kw)
            finally:
                _elastic.watchdog_disarm()
        self._logical_steps += kw
        self._window_pos = 0
        return out

    def step_one(self, *batch, batch_size=None):
        """Single-logical-step escape on a K>1 program: runs ONE step as
        a ``k_window=1`` window (its own compiled entry, registered as a
        declared warmup — never a steady-state guard violation) and moves
        the window cursor off the K boundary; ``Trainer.save_states``
        refuses until further ``step_one`` calls complete a whole window.
        On a K=1 program this is exactly ``__call__``."""
        if self._k == 1:
            return self(*batch, batch_size=batch_size)
        nds = [b if isinstance(b, NDArray) else NDArray(jnp.asarray(b))
               for b in batch]
        if batch_size is None:
            batch_size = nds[0].shape[0]
        window = [NDArray(nd._data[None]) for nd in nds]
        pos = self._window_pos
        out = self(*window, batch_size=batch_size)
        self._window_pos = (pos + 1) % self._k
        return NDArray(out._data[0])

    def sync(self):
        """Write fold-held state back into the live Parameters/Trainer
        (no-op for the local fold, which swaps buffers every step; the
        multi-process fold keeps donated global registers and syncs
        lazily — ``Trainer.save_states`` calls this)."""
        if self._dist is not None:
            self._dist.sync_out()

    def invalidate(self):
        """Drop compiled programs and (dist) registers so the next call
        re-stages from the live Parameters — required after
        ``load_states`` or direct ``set_data`` on a multi-process fold."""
        self._cache.clear()
        self._dist = None

    # -- fallback path ---------------------------------------------------
    def _note_fallback(self, reason, label="capture-failure"):
        if self._dist is not None:
            # the registers hold the live trajectory; the eager path reads
            # the Parameters — refresh them before switching over
            self._dist.sync_out()
            self._dist = None
        self._fallback_reason = reason
        self._fallback_label = label
        if not self._warned:
            self._warned = True
            _warnings.warn(
                f"step fold disabled ({reason}); running the eager "
                "record/backward/step path instead — see docs/step_fold.md",
                UserWarning, stacklevel=3)

    def _run_eager(self, nds, batch_size):
        """Route a fallback to the right eager shape: stacked windows for
        a K>1 program, the single-batch path otherwise.  ``nds`` must
        match the shape the caller was dispatched with."""
        if self._k > 1 and nds and nds[0].ndim >= 2:
            return self._eager_window(nds, batch_size)
        return self._eager_step(nds, batch_size)

    def _eager_step(self, nds, batch_size):
        """The unfused reference path: record forward+loss, tape backward,
        ``Trainer.step`` (allreduce + fused optimizer groups).  EVERY
        eager execution through the program counts in
        ``step_fold_fallback`` (with a per-reason label) — the counter
        quantifies how much of a run escaped the fold, not how many
        distinct reasons there were."""
        _profiler.incr_labeled("step_fold_fallback",
                               self._fallback_label or "deferred-init")
        with autograd.record():
            loss = self._loss_fn(*nds)
        autograd.backward([loss])
        self._trainer.step(batch_size)
        return loss

    def _eager_window(self, nds, batch_size):
        """Eager reference for a stacked ``[k_window, ...]`` window: one
        unfused step per row, losses restacked to the folded program's
        ``[k_window, ...]`` output shape."""
        losses = []
        for j in range(int(nds[0].shape[0])):
            row = [NDArray(nd._data[j]) for nd in nds]
            losses.append(self._eager_step(row, batch_size))
        return NDArray(jnp.stack([l._data for l in losses]))

    # -- the folded step -------------------------------------------------
    def _folded_step(self, nds, batch_size, k_window=None):
        tr = self._trainer
        opt = tr._optimizer
        kw = k_window if self._k > 1 else None
        tr._check_and_rescale_grad(tr._scale / batch_size)
        touched = []
        for i, p in enumerate(tr._params):
            if p.grad_req == "null" or p._data is None:
                continue
            if p._data._grad is None:
                raise UserWarning(
                    f"Gradient of Parameter `{p.name}` has no grad buffer")
            if p.grad_req != "write":
                # grad_req='add' accumulates across backwards — a folded
                # step would overwrite the running sum
                self._note_fallback(f"{p.name} has grad_req="
                                    f"{p.grad_req!r} (fold needs 'write')",
                                    label="grad-req-add")
                return self._run_eager(nds, batch_size)
            if i not in tr._states:
                tr._states[i] = opt.create_state_multi_precision(i, p.data())
            touched.append((i, p))
        tr._account_memory(touched)
        groups, rest = _fused.plan_groups(
            opt, [(i, p.data(), None) for i, p in touched], tr._states)
        if rest or not groups:
            names = [tr._params[i].name for i, _, _ in rest][:3]
            self._note_fallback(
                f"no fused kernels for {type(opt).__name__} on "
                f"{names or 'these params'} (lazy/sparse or unsupported)",
                label="unsupported-optimizer")
            return self._run_eager(nds, batch_size)

        # kvstore routing: a dist store either folds in-program (SPMD
        # collectives available) or forces the eager path (async PS —
        # server-side optimizer, host TCP wire)
        kv = tr._kvstore
        dist = kv is not None and kv.num_workers > 1
        if dist and not (hasattr(kv, "_worker_mesh")
                         and kv.supports_grad_bucketing()):
            self._note_fallback(
                f"kvstore {getattr(kv, 'type', kv)!r} cannot fold "
                "(server-side optimizer / async tier)", label="async-PS")
            return self._run_eager(nds, batch_size)

        tpos_of = {i: t for t, (i, _) in enumerate(touched)}
        group_sig = tuple(
            (step.__name__, dt, cx,
             tuple(i for i, _, _, _ in members),
             tuple(len(flat) for _, _, _, flat in members))
            for (step, dt, cx), members in groups.items())
        raws = [_raw(nd) for nd in nds]
        batch_sig = tuple((tuple(a.shape), str(a.dtype)) for a in raws)
        key_sig = (batch_sig, group_sig, bool(dist), kw)

        entry = self._cache.get(key_sig)
        fresh = entry is None
        if fresh:
            try:
                entry = self._build(raws, touched, groups, tpos_of, dist,
                                    kv, kw=kw)
            except Exception as e:  # capture failure: loud sticky fallback
                self._note_fallback(f"capture failed: {e!r:.200}")
                return self._run_eager(nds, batch_size)
            self._cache[key_sig] = entry

        # per-step dynamic hypers: bump ALL counts first, then read lr/wd
        # (the fused_update discipline — synchronized params all see the
        # same num_update).  For a K-window, repeat the discipline once
        # per LOGICAL step so the stacked [K, n] rows are exactly what K
        # unfolded steps would have staged — and draw K keys from the
        # ambient stream in step order so dropout parity is bit-exact.
        if kw is None:
            for i, _ in touched:
                opt._update_count(i)
            lrs = jnp.asarray([opt._get_lr(i) for i, _ in touched],
                              jnp.float32)
            wds = jnp.asarray([opt._get_wd(i) for i, _ in touched],
                              jnp.float32)
            ts = jnp.asarray([opt._index_update_count[i]
                              for i, _ in touched], jnp.float32)
            key = get_key()
        else:
            lr_rows, wd_rows, t_rows, keys = [], [], [], []
            for _ in range(kw):
                for i, _p in touched:
                    opt._update_count(i)
                lr_rows.append([opt._get_lr(i) for i, _p in touched])
                wd_rows.append([opt._get_wd(i) for i, _p in touched])
                t_rows.append([opt._index_update_count[i]
                               for i, _p in touched])
                keys.append(get_key())
            lrs = jnp.asarray(lr_rows, jnp.float32)
            wds = jnp.asarray(wd_rows, jnp.float32)
            ts = jnp.asarray(t_rows, jnp.float32)
            key = jnp.stack(keys)
        scalars = {k: jnp.asarray(v, jnp.float32)
                   for k, v in _fused._scalars(opt).items()}

        return self._dispatch(entry, touched, key, lrs, wds, ts, scalars,
                              raws, fresh, kw)

    def _dispatch(self, entry, touched, key, lrs, wds, ts, scalars, raws,
                  fresh, kw=None):
        tr = self._trainer
        site = "gluon.step_fold" if kw is None else "gluon.step_fold_k"
        if self._dist is not None:
            call_args = self._dist.stage_call(key, lrs, wds, ts, scalars,
                                              raws, window=kw is not None)
        else:
            param_arrs = [_raw(p._data) for p in entry["params"]]
            state_arrs = [tuple(_raw(s) for s in flat)
                          for flat in entry["state_flats"]]
            call_args = (key, lrs, wds, ts, scalars, param_arrs, state_arrs,
                         *raws)
        tc = _perf() if fresh else None
        t0 = _perf() if _profiler._active else None
        try:
            try:
                out = entry["fn"](*call_args)
            except Exception as e:
                # the donated whole-step dispatch is an OOM choke point
                _profiler.maybe_oom_postmortem(e, site)
                raise
            loss_local = self._wire_outputs(entry, touched, out)
            if tc is not None:
                # AFTER output wiring: a guard in raise mode must never
                # leave Parameters pointing at donated-and-deleted buffers.
                # Tail-window / step_one entries (k_window != k) are a
                # DECLARED warmup: each distinct window width is its own
                # program, built once — register the compile but don't let
                # an armed guard judge it (the serving re-warm convention).
                if entry.get("declared_warmup"):
                    with _profiler.compile_guard_paused():
                        _profiler.record_compile(
                            site, self._compile_sig(entry, raws),
                            (_perf() - tc) * 1e3)
                else:
                    _profiler.record_compile(
                        site, self._compile_sig(entry, raws),
                        (_perf() - tc) * 1e3)
            ca = entry.get("comm_args")
            kk = int(kw) if kw is not None else 1
            if ca is not None:
                from ..comm import compression as _comp

                _comp.account(ca["bytes_raw"] * kk, ca["bytes_wire"] * kk)
                if ca["hops"]:
                    _profiler.incr("comms_ring_hops", ca["hops"] * kk)
            if t0 is not None:
                span_args = {"params": len(touched),
                             "dist": self._dist is not None}
                if kw is not None:
                    span_args["k"] = int(kw)
                if ca is not None:
                    span_args.update(ca,
                                     bytes_raw=ca["bytes_raw"] * kk,
                                     bytes_wire=ca["bytes_wire"] * kk)
                _profiler.record_span("trainer.step_fold", "trainer", t0,
                                      args=span_args)
            _profiler.incr("step_fold_call")
            # freshness snapshot (Trainer._update parity): only a future
            # backward/user write may flip a param back to fresh
            for i, p in touched:
                tr._grad_versions[i] = p.grad_version
        finally:
            _profiler.step_boundary()
        if not self._guard_armed:
            self._guard_armed = True
            _profiler.arm_compile_guard(site)
        return loss_local

    def _compile_sig(self, entry, raws):
        kw = entry.get("k")
        program = "step_fold" if not kw else f"step_fold_k[{kw}]"
        if entry["dist"]:
            program += ":dist"
            ca = entry.get("comm_args")
            if ca:
                # a wire-policy change (codec tier or exchange algorithm)
                # is a DISTINCT program, not a recompile of the old one —
                # the same reason bucket keys are codec-namespaced
                program += f":{ca.get('codec')}:{ca.get('algo')}"
        sig = {"__program__": program,
               "params": _profiler.sig_static(len(entry["params"])),
               "groups": _profiler.sig_static(
                   [g[0] for g in entry["plan_names"]])}
        for i, a in enumerate(raws):
            sig[f"in{i}"] = {"k": "array", "shape": tuple(a.shape),
                             "dtype": str(a.dtype)}
        return sig

    def _warn_foreign_aux(self, aux_cell):
        """One loud warning when the capture saw aux updates for params
        the trainer doesn't own: their OLD value is a baked trace
        constant, so they stay FROZEN in-fold (pass the block's full
        ``collect_params()`` to the Trainer to fold them)."""
        foreign = aux_cell[0][1] if aux_cell else []
        if foreign:
            _warnings.warn(
                "step fold: aux updates for parameters the Trainer does "
                f"not own stay FROZEN inside the fold ({foreign[:3]}...); "
                "construct the Trainer with the block's full "
                "collect_params() to fold their running stats — "
                "docs/step_fold.md", UserWarning, stacklevel=4)

    def _wire_outputs(self, entry, touched, out):
        """Swap the program's fresh buffers into the live NDArrays (local
        fold) or registers (dist fold).  Returns the loss NDArray."""
        if self._dist is not None:
            return self._dist.wire(entry, touched, out, self._keep_grads)
        it = iter(out)
        new_params, new_states, loss_data = next(it), next(it), next(it)
        grads = next(it) if self._keep_grads else None
        for p, arr in zip(entry["params"], new_params):
            _swap(p._data, arr)
        for flat, new in zip(entry["state_flats"], new_states):
            for s_nd, s_new in zip(flat, new):
                _swap(s_nd, s_new)
        if grads is not None:
            for (_, p), g in zip(touched, grads):
                _swap(p._data._grad, g)
        return NDArray(loss_data)

    # -- capture ---------------------------------------------------------
    def _build(self, raws, touched, groups, tpos_of, dist, kv, kw=None):
        """Trace + jit the whole step.  Returns the cache entry dict.  The
        capture is validated with ``jax.eval_shape`` (no device work), so
        a loss_fn the tracer cannot swallow fails HERE — cleanly — and the
        caller falls back to the eager path.

        With ``kw`` (the K-step fold), the SAME per-step body — forward,
        backward, bucket collectives, optimizer tail, aux write-back —
        becomes the body of a ``jax.lax.scan`` over the ``[kw, ...]``
        stacked batch window: params and optimizer state (and, dist, EF
        residuals) ride the loop carry; per-step lr/wd/t rows and PRNG
        keys ride as stacked ``[kw, ...]`` scan inputs; the per-step
        losses stack as the scan output."""
        tr = self._trainer
        params = [p for p in tr._params if p._data is not None]
        slot_of = {id(p): s for s, p in enumerate(params)}
        trainable_slots = [slot_of[id(p)] for _, p in touched]
        state_flats = [None] * len(touched)
        plan = []        # (step_fn, [(tpos, slot)])
        plan_names = []
        for (step, dt, cx), members in groups.items():
            rows = []
            for i, w, _, flat in members:
                t = tpos_of[i]
                state_flats[t] = tuple(flat)
                rows.append((t, slot_of[id(tr._params[i])]))
            plan.append((step, tuple(rows)))
            plan_names.append((step.__name__, dt, len(members)))
        loss_fn = self._loss_fn
        keep_grads = self._keep_grads
        aux_cell = []     # [(in_slots, out_params)] discovered on trace 1
        loss_meta = []    # [ndim] of the user loss

        def forward_loss(train_arrs, full_arrs, key, batch):
            full = list(full_arrs)
            for s, arr in zip(trainable_slots, train_arrs):
                full[s] = arr
            with trace_scope(params, full, key, True) as collector:
                loss = loss_fn(*[NDArray(b) for b in batch])
            loss_data = loss._data
            if not loss_meta:
                loss_meta.append(loss_data.ndim)
            if not aux_cell:
                # per-POSITION ownership (slot index, or None for a param
                # the trainer doesn't hold): owned and foreign aux may
                # interleave in forward order.  Foreign aux updates are
                # DROPPED, not written back — the old value is baked into
                # the trace as a constant, so a write-back would keep
                # re-deriving the update from the original stats forever
                # (frozen is honest; a warning surfaces it at build).
                kinds, foreign = [], []
                for p, _ in collector:
                    s = slot_of.get(id(p))
                    kinds.append(s)
                    if s is None:
                        foreign.append(p.name)
                aux_cell.append((kinds, foreign))
            aux_vals = tuple(v._data if isinstance(v, NDArray) else v
                             for _, v in collector)
            # differentiate the SUM in the loss's own dtype — exact parity
            # with loss.backward()'s implicit ones head-grads
            return jnp.sum(loss_data), (aux_vals, loss_data)

        def optimizer_tail(param_arrs, state_arrs, grads, lrs, wds, ts,
                           scalars):
            new_full = list(param_arrs)
            new_states = list(state_arrs)
            for step, rows in plan:
                for t, s in rows:
                    nw, ns = step(param_arrs[s], grads[t], state_arrs[t],
                                  lrs[t], wds[t], ts[t], scalars)
                    new_full[s] = nw
                    new_states[t] = tuple(ns)
            return new_full, new_states

        def apply_aux(new_full, param_arrs, aux_vals):
            kinds, _ = aux_cell[0]
            for s, v in zip(kinds, aux_vals):
                if s is not None:
                    new_full[s] = v.astype(param_arrs[s].dtype)

        if dist:
            return self._build_dist(raws, touched, params, state_flats,
                                    plan, plan_names, trainable_slots,
                                    forward_loss, optimizer_tail, apply_aux,
                                    aux_cell, loss_meta, kv, kw=kw)

        def one_step(key, lr, wd, t, scalars, param_arrs, state_arrs,
                     batch):
            """ONE logical step — shared verbatim by the K=1 program and
            the scan body, so folded numerics cannot depend on K."""
            train_arrs = [param_arrs[s] for s in trainable_slots]
            (_, (aux_vals, loss_data)), grads = jax.value_and_grad(
                forward_loss, has_aux=True)(train_arrs, list(param_arrs),
                                            key, batch)
            new_full, new_states = optimizer_tail(
                param_arrs, state_arrs, grads, lr, wd, t, scalars)
            apply_aux(new_full, param_arrs, aux_vals)
            return new_full, new_states, loss_data, grads

        if kw is None:
            def pure_step(key, lrs, wds, ts, scalars, param_arrs,
                          state_arrs, *batch):
                new_full, new_states, loss_data, grads = one_step(
                    key, lrs, wds, ts, scalars, param_arrs, state_arrs,
                    batch)
                out = (new_full, new_states, loss_data)
                if keep_grads:
                    out += (list(grads),)
                return out
        else:
            def pure_step(keys, lrs, wds, ts, scalars, param_arrs,
                          state_arrs, *windows):
                def body(carry, xs):
                    p_arrs, s_arrs = carry[0], carry[1]
                    key, lr, wd, t = xs[0], xs[1], xs[2], xs[3]
                    batch = xs[4:]
                    new_full, new_states, loss_data, grads = one_step(
                        key, lr, wd, t, scalars, list(p_arrs),
                        [tuple(s) for s in s_arrs], batch)
                    new_carry = (tuple(new_full),
                                 tuple(tuple(s) for s in new_states))
                    if keep_grads:
                        new_carry += (tuple(grads),)
                    return new_carry, loss_data

                init = (tuple(param_arrs),
                        tuple(tuple(s) for s in state_arrs))
                if keep_grads:
                    init += (tuple(jnp.zeros_like(param_arrs[s])
                                   for s in trainable_slots),)
                xs = (keys, lrs, wds, ts) + tuple(windows)
                carry, losses = jax.lax.scan(body, init, xs)
                out = (list(carry[0]),
                       [tuple(s) for s in carry[1]], losses)
                if keep_grads:
                    out += (list(carry[2]),)
                return out

        # abstract validation pass — populates aux_cell/loss_meta and
        # surfaces capture failures without any device work.  The key aval
        # comes from a FRESH PRNGKey(0), never get_key(): splitting the
        # ambient stream at build time would desync fold-vs-unfused
        # dropout parity by one key.
        ex_key = jax.random.PRNGKey(0)
        hyp = ((len(touched),) if kw is None else (kw, len(touched)))
        key_shape = ex_key.shape if kw is None else (kw,) + ex_key.shape
        abstract = (
            jax.ShapeDtypeStruct(key_shape, ex_key.dtype),
            jax.ShapeDtypeStruct(hyp, jnp.float32),
            jax.ShapeDtypeStruct(hyp, jnp.float32),
            jax.ShapeDtypeStruct(hyp, jnp.float32),
            {k: jax.ShapeDtypeStruct((), jnp.float32)
             for k in _fused._scalars(tr._optimizer)},
            [jax.ShapeDtypeStruct(p._data.shape, p._data.dtype)
             for p in params],
            [tuple(jax.ShapeDtypeStruct(s.shape, s.dtype)
                   for s in flat) for flat in state_flats],
            *[jax.ShapeDtypeStruct(a.shape, a.dtype) for a in raws],
        )
        jax.eval_shape(pure_step, *abstract)
        self._warn_foreign_aux(aux_cell)
        donate = (5, 6) if _fused.donation_enabled() else ()
        if kw is not None and self._donate_window and \
                _fused.donation_enabled():
            # the staged [K, ...] window is single-use — donating it back
            # to the allocator covers the stacked copy's footprint
            donate += tuple(range(7, 7 + len(raws)))
        fn = jax.jit(pure_step, donate_argnums=donate)
        return {"fn": fn, "params": params, "state_flats": state_flats,
                "plan_names": plan_names, "dist": False, "k": kw,
                "declared_warmup": kw is not None and kw != self._k,
                "abstract": abstract}

    # -- the multi-process (in-fold collectives) build -------------------
    def _build_dist(self, raws, touched, params, state_flats, plan,
                    plan_names, trainable_slots, forward_loss,
                    optimizer_tail, apply_aux, aux_cell, loss_meta, kv,
                    kw=None):
        """Fold the gradient exchange into the program: forward/backward
        per worker shard under ONE ``shard_map`` over the kvstore's worker
        mesh, with each size-capped gradient bucket an explicit allreduce
        node (fp32 ``psum``, or the PR 14 codec's in-program quantized
        exchange) that XLA may schedule as soon as that bucket's grads
        exist — comms overlapped against the remaining backward.  The
        optimizer tail then runs on the replicated reduced grads."""
        from jax.sharding import PartitionSpec as P

        from .. import kvstore as kv_mod
        from ..comm import compression as comp_mod

        tr = self._trainer
        mesh = kv._worker_mesh()
        keep_grads = self._keep_grads
        policy = comp_mod.resolve_policy()
        ef = policy is not None and policy.error_feedback

        # THE deterministic bucket rule (kvstore.plan_buckets — shared
        # with bucketed_pushpull and the overlap hook, so in-fold and
        # out-of-fold paths can never draw different bucket boundaries);
        # positions index ``touched`` order = the grads list
        _, kv_buckets = kv_mod.plan_buckets(
            [(i, p.grad()) for i, p in touched],
            names=[p.name for _, p in touched], compression=policy)
        buckets = []   # (codec|None, [(tpos, off, n, shape)])
        for bk in kv_buckets:
            rows, off = [], 0
            for t in bk["positions"]:
                a = touched[t][1]._data._data
                rows.append((t, off, int(a.size), tuple(a.shape)))
                off += int(a.size)
            buckets.append((bk["codec"], tuple(rows)))
        n_train = len(touched)
        algo = policy.algo if policy is not None else "psum"
        P0 = P()
        PW = P("w")
        # per-LOGICAL-step batch spec: inside a K-window the scan body
        # sees one [global_batch, ...] slice per iteration (the stacked
        # window itself is sharded on axis 1, its batch axis)
        batch_specs = tuple(
            P(*(("w",) + (None,) * ((a.ndim - (2 if kw else 1)))))
            for a in raws)

        def shard_body(train_arrs, full_arrs, key, residuals, *batch):
            # distinct PRNG stream per worker — the documented dist-fold
            # convention (matches the SPMD quantized-collective build)
            key = jax.random.fold_in(key, jax.lax.axis_index("w"))
            (_, (aux_vals, loss_data)), grads = jax.value_and_grad(
                forward_loss, has_aux=True)(train_arrs, full_arrs, key,
                                            batch)
            new_grads = [None] * n_train
            new_resid = []
            ri = 0
            for codec, rows in buckets:
                flat = jnp.concatenate(
                    [grads[t].reshape(-1) for t, _, _, _ in rows])
                if codec is None:
                    red = jax.lax.psum(flat, "w")
                else:
                    red, resid = comp_mod.traced_allreduce(
                        codec, flat, residuals[ri][0] if ef else None,
                        ("w",), algo=algo)
                    if ef:
                        new_resid.append(resid[None, :])
                        ri += 1
                for t, off, n, shape in rows:
                    new_grads[t] = red[off:off + n].reshape(shape)
            # local loss leaves sharded over 'w' (each worker reads its
            # own shard — parity with the per-worker eager loss); aux
            # stats pmean so every worker applies the same running stats
            loss_out = loss_data if loss_data.ndim >= 1 \
                else loss_data[None]
            aux_vals = tuple(jax.lax.pmean(a, "w") for a in aux_vals)
            return (tuple(new_grads), tuple(new_resid), loss_out, aux_vals)

        # ring outputs are replicated by explicit ppermute relay, which
        # the static replication checker cannot infer through
        mapped = jax.shard_map(
            shard_body, mesh=mesh,
            in_specs=(P0, P0, P0, PW) + batch_specs,
            out_specs=(P0, PW, PW, P0), check_vma=(algo != "ring"))

        def dist_step(key, lr, wd, t, scalars, param_arrs, state_arrs,
                      residuals, batch):
            """ONE logical dist step (shard_map'd collectives inside) —
            shared verbatim by the K=1 program and the scan body."""
            train_arrs = [param_arrs[s] for s in trainable_slots]
            grads_t, new_resid, loss_out, aux_vals = mapped(
                train_arrs, list(param_arrs), key, tuple(residuals), *batch)
            new_full, new_states = optimizer_tail(
                param_arrs, state_arrs, list(grads_t), lr, wd, t, scalars)
            apply_aux(new_full, param_arrs, aux_vals)
            return new_full, new_states, list(new_resid), loss_out, grads_t

        if kw is None:
            def pure_step(key, lrs, wds, ts, scalars, param_arrs,
                          state_arrs, residuals, *batch):
                new_full, new_states, new_resid, loss_out, grads_t = \
                    dist_step(key, lrs, wds, ts, scalars, param_arrs,
                              state_arrs, residuals, batch)
                out = (new_full, new_states, new_resid, loss_out)
                if keep_grads:
                    out += (list(grads_t),)
                return out
        else:
            def pure_step(keys, lrs, wds, ts, scalars, param_arrs,
                          state_arrs, residuals, *windows):
                def body(carry, xs):
                    p_arrs, s_arrs, resid = carry
                    key, lr, wd, t = xs[0], xs[1], xs[2], xs[3]
                    batch = xs[4:]
                    new_full, new_states, new_resid, loss_out, grads_t = \
                        dist_step(key, lr, wd, t, scalars, list(p_arrs),
                                  [tuple(s) for s in s_arrs], list(resid),
                                  batch)
                    new_carry = (tuple(new_full),
                                 tuple(tuple(s) for s in new_states),
                                 tuple(new_resid))
                    ys = (loss_out,)
                    if keep_grads:
                        ys += (tuple(grads_t),)
                    return new_carry, ys

                init = (tuple(param_arrs),
                        tuple(tuple(s) for s in state_arrs),
                        tuple(residuals))
                xs = (keys, lrs, wds, ts) + tuple(windows)
                carry, ys = jax.lax.scan(body, init, xs)
                out = (list(carry[0]), [tuple(s) for s in carry[1]],
                       list(carry[2]), ys[0])
                if keep_grads:
                    # last logical step's grads — the window-boundary
                    # grads, same contract as K=1's post-step grads
                    out += ([g[-1] for g in ys[1]],)
                return out

        if self._dist is not None:
            # a rebuild (new batch signature): the live Parameters are
            # stale — refresh them from the old registers before re-staging
            self._dist.sync_out()
        regs = _DistRegisters(tr, params, state_flats, mesh,
                              buckets if ef else [], loss_meta)
        self._dist = regs
        donate = (5, 6, 7) if _fused.donation_enabled() else ()
        if kw is not None and self._donate_window and \
                _fused.donation_enabled():
            donate += tuple(range(8, 8 + len(raws)))
        with mesh:
            fn = jax.jit(pure_step, donate_argnums=donate)
        # validation trace (abstract; global shapes)
        ex_key = jax.random.PRNGKey(0)
        nw = mesh.devices.size
        hyp = ((n_train,) if kw is None else (kw, n_train))
        key_shape = ex_key.shape if kw is None else (kw,) + ex_key.shape
        if kw is None:
            batch_avals = [jax.ShapeDtypeStruct(
                (a.shape[0] * nw,) + tuple(a.shape[1:]), a.dtype)
                for a in raws]
        else:
            batch_avals = [jax.ShapeDtypeStruct(
                (a.shape[0], a.shape[1] * nw) + tuple(a.shape[2:]),
                a.dtype) for a in raws]
        abstract = (
            jax.ShapeDtypeStruct(key_shape, ex_key.dtype),
            jax.ShapeDtypeStruct(hyp, jnp.float32),
            jax.ShapeDtypeStruct(hyp, jnp.float32),
            jax.ShapeDtypeStruct(hyp, jnp.float32),
            {k: jax.ShapeDtypeStruct((), jnp.float32)
             for k in _fused._scalars(tr._optimizer)},
            [jax.ShapeDtypeStruct(p._data.shape, p._data.dtype)
             for p in params],
            [tuple(jax.ShapeDtypeStruct(s.shape, s.dtype)
                   for s in flat) for flat in state_flats],
            [jax.ShapeDtypeStruct((nw, n), jnp.float32)
             for n in regs.resid_sizes],
            *batch_avals,
        )
        with mesh:
            jax.eval_shape(pure_step, *abstract)
        self._warn_foreign_aux(aux_cell)
        # per-dispatch comms accounting for the in-fold exchange (the
        # trace_report comms table + counters): logical payload sizes per
        # LOGICAL step, plus hop-level detail when the ring algorithm
        # carries the buckets over explicit ppermute
        from ..comm import ring as ring_mod

        b_raw = b_wire = hops = hop_wire = hop_fp32 = 0
        codec_ids = []
        for codec, rows in buckets:
            n = sum(r[2] for r in rows)
            b_raw += 4 * n
            if codec is None:
                b_wire += 4 * n
            else:
                b_wire += int(codec.wire_nbytes(n))
                codec_ids.append(codec.id)
                if algo == "ring":
                    h, bb = ring_mod.hop_plan(codec, n, nw)
                    hops += h
                    hop_wire += h * bb
                    # what a fp32 ring would move per hop: one raw chunk
                    hop_fp32 += h * 4 * ring_mod._ring_chunk(codec, n, nw)
        comm_args = None
        if codec_ids:
            comm_args = {"bytes_raw": int(b_raw), "bytes_wire": int(b_wire),
                         "codec": ",".join(sorted(set(codec_ids))),
                         "algo": algo, "hops": int(hops),
                         "bytes_hop": int(hop_wire // hops) if hops else 0,
                         "bytes_hop_fp32":
                             int(hop_fp32 // hops) if hops else 0}
        return {"fn": fn, "params": params, "state_flats": state_flats,
                "plan_names": plan_names, "dist": True, "k": kw,
                "declared_warmup": kw is not None and kw != self._k,
                "comm_args": comm_args, "abstract": abstract}


class _DistRegisters:
    """Donated global registers for the multi-process fold: replicated
    params/optimizer state and sharded error-feedback residuals live as
    jax global arrays across steps (zero per-step staging); Parameters and
    ``trainer._states`` are refreshed lazily via ``sync_out``."""

    def __init__(self, trainer, params, state_flats, mesh, ef_buckets,
                 loss_meta):
        from jax.sharding import NamedSharding, PartitionSpec as P

        self._trainer = trainer
        self._params = params
        self._state_flats = state_flats
        self._mesh = mesh
        self._loss_meta = loss_meta
        self._rep = NamedSharding(mesh, P())
        self._row = NamedSharding(mesh, P("w"))
        self.param_arrays = [self._replicate(_raw(p._data)) for p in params]
        self.state_arrays = [tuple(self._replicate(_raw(s)) for s in flat)
                             for flat in state_flats]
        self.resid_sizes = [sum(n for _, _, n, _ in rows)
                            for codec, rows in ef_buckets
                            if codec is not None]
        # error-feedback residuals persist through the trainer's
        # ErrorFeedback store (the PR 14 contract: save_states carries
        # them, a rebuild re-stages them — never silently zeroed); each
        # process stages its OWN local rows, per-host-file style
        import jax as _jax

        nw = mesh.devices.size
        local_rows = max(1, nw // _jax.process_count())
        self.residuals = []
        for b, n in enumerate(self.resid_sizes):
            local = None
            fb = trainer._grad_feedback
            if fb is not None:
                stored = fb._res.get(self._resid_key(b, n))
                if stored is not None and \
                        tuple(_np.shape(stored)) == (local_rows, n):
                    local = _np.asarray(stored, _np.float32)
            if local is None:
                local = _np.zeros((local_rows, n), _np.float32)
            self.residuals.append(self._stage_rows(local))

    def _replicate(self, arr):
        import jax as _jax

        local = _jax.device_put(_np.asarray(arr),
                                self._mesh.local_devices[0])
        return _jax.make_array_from_single_device_arrays(
            tuple(local.shape), self._rep, [local])

    @staticmethod
    def _resid_key(b, n):
        return f"__fold_dist__:{b}:{n}"

    def _stage_rows(self, local):
        """This process's residual rows -> the 'w'-sharded global array."""
        import jax as _jax

        if _jax.process_count() == 1:
            return _jax.device_put(local, self._row)
        return _jax.make_array_from_process_local_data(self._row, local)

    def _global_batch(self, arr, window=False):
        import jax as _jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        # a [K, batch, ...] window shards on its BATCH axis (axis 1); a
        # plain batch shards on axis 0
        if window:
            spec = P(*((None, "w") + (None,) * (arr.ndim - 2)))
        else:
            spec = P(*(("w",) + (None,) * (arr.ndim - 1)))
        sharding = NamedSharding(self._mesh, spec)
        return _jax.make_array_from_process_local_data(
            sharding, _np.asarray(arr))

    def stage_call(self, key, lrs, wds, ts, scalars, raws, window=False):
        rep = self._replicate
        return (rep(key), rep(lrs), rep(wds), rep(ts),
                {k: rep(v) for k, v in scalars.items()},
                self.param_arrays, self.state_arrays, self.residuals,
                *[self._global_batch(a, window=window) for a in raws])

    def wire(self, entry, touched, out, keep_grads):
        # everything stays DEVICE-RESIDENT: addressable_data(0) hands back
        # this process's shard buffer without a host sync — an immediate
        # np.asarray here would block dispatch on the whole step's device
        # completion every step and forfeit the overlap the fold buys
        # (the PR 12 MoE-extras lesson); sync_out() is the host boundary
        it = iter(out)
        new_params, new_states, new_resid, loss_out = (
            next(it), next(it), next(it), next(it))
        grads = next(it) if keep_grads else None
        self.param_arrays = new_params
        self.state_arrays = [tuple(s) for s in new_states]
        self.residuals = list(new_resid)
        if grads is not None:
            for (_, p), g in zip(touched, grads):
                p._data._grad._data = g.addressable_data(0)
                p._data._grad._version += 1
        local = loss_out.addressable_data(0)
        kw = entry.get("k")
        if self._loss_meta and self._loss_meta[0] == 0:
            # scalar user loss: [1] per worker, or [K, 1] stacked
            local = local.reshape((kw,) if kw else ())
        return NDArray(local)

    def sync_out(self):
        """Fold registers -> live Parameters / trainer states (gathered
        off the mesh so eager ops see single-device arrays).  Residuals
        land in the trainer's ErrorFeedback store so ``save_states``
        persists them and a rebuild re-stages them."""
        with autograd.pause():
            for p, a in zip(self._params, self.param_arrays):
                p._data._data = jnp.asarray(_np.asarray(
                    a.addressable_data(0)))
                p._data._version += 1
            for flat, arrs in zip(self._state_flats, self.state_arrays):
                for s_nd, a in zip(flat, arrs):
                    s_nd._data = jnp.asarray(_np.asarray(
                        a.addressable_data(0)))
                    s_nd._version += 1
        if self.residuals:
            from ..comm import compression as comp_mod

            tr = self._trainer
            if tr._grad_feedback is None:
                tr._grad_feedback = comp_mod.ErrorFeedback()
            for b, (n, arr) in enumerate(zip(self.resid_sizes,
                                             self.residuals)):
                tr._grad_feedback.update(
                    self._resid_key(b, n),
                    _np.asarray(arr.addressable_data(0)))


class EvalProgram:
    """The folded evaluation pass (``Trainer.fold_eval(loss_fn, k)``).

    Calling the program with a batch (K=1) or a ``[K, batch, ...]``
    stacked window (``pipeline.stage_window(k)``) runs forward-only loss
    under the SAME ``trace_scope`` ceremony as the training fold — but
    with ``is_training=False``, so BatchNorm reads running stats and
    dropout is identity — and accumulates the summed loss IN-PROGRAM
    into a device-resident f32 register.  The host reads nothing until
    :meth:`result`, once per eval pass: an N-batch eval is N/K dispatches
    and ONE device->host transfer.

    Compile site ``gluon.fold_eval``; every fresh build registers as a
    declared warmup (eval programs are built once per batch signature,
    usually after the train guard armed).  Escape hatches and fallback
    accounting (``step_fold_fallback`` labels) match :class:`StepProgram`.
    """

    def __init__(self, trainer, loss_fn, block=None, k=None):
        self._trainer = trainer
        self._loss_fn = loss_fn
        self._block = block
        self._k = max(1, int(k if k is not None else fold_k()))
        self._cache = {}          # (batch sig, kw) -> entry
        self._fallback_reason = None
        self._fallback_label = None
        self._warned = False
        self._guard_armed = False
        self._acc = None          # device f32 scalar — summed loss so far
        self._host_sum = 0.0      # eager-path contribution
        self._count = 0           # loss elements accumulated
        self._synced_at = -1      # train-fold progress at last register sync
        if not fold_enabled():
            self._fallback_reason = "MXNET_STEP_FOLD=0"
            self._fallback_label = "env-off"
        elif _engine.is_naive():
            self._fallback_reason = "NaiveEngine"
            self._fallback_label = "naive-engine"
        elif _opted_out(block):
            self._fallback_reason = "block opt-out (_step_fold_opt_out)"
            self._fallback_label = "block-opt-out"

    @property
    def folded(self):
        return self._fallback_reason is None

    @property
    def fallback_reason(self):
        return self._fallback_reason

    @property
    def k(self):
        return self._k

    @property
    def count(self):
        """Loss elements accumulated since the last ``result(reset=True)``."""
        return self._count

    def _note_fallback(self, reason, label="capture-failure"):
        self._fallback_reason = reason
        self._fallback_label = label
        if not self._warned:
            self._warned = True
            _warnings.warn(
                f"eval fold disabled ({reason}); running the eager "
                "forward path instead — see docs/step_fold.md",
                UserWarning, stacklevel=3)

    def _sync_train_fold(self):
        """A multi-process TRAIN fold keeps the live trajectory in donated
        registers — pull them back into the Parameters once per train
        progress before evaluating against them."""
        ref = getattr(self._trainer, "_fold", None)
        fold = ref() if ref is not None else None
        if fold is not None and fold._dist is not None and \
                fold._logical_steps != self._synced_at:
            fold.sync()
            self._synced_at = fold._logical_steps

    def __call__(self, *batch):
        nds = [b if isinstance(b, NDArray) else NDArray(jnp.asarray(b))
               for b in batch]
        self._sync_train_fold()
        tr = self._trainer
        if self._fallback_reason is not None or any(
                p._deferred_init is not None or p._data is None
                for p in tr._params):
            return self._eager_eval(nds)
        return self._folded_eval(nds)

    def _eager_eval(self, nds):
        _profiler.incr_labeled("step_fold_fallback",
                               self._fallback_label or "deferred-init")
        rows = [nds]
        if self._k > 1 and nds and nds[0].ndim >= 2:
            kw = int(nds[0].shape[0])
            rows = [[NDArray(nd._data[j]) for nd in nds]
                    for j in range(kw)]
        with autograd.pause():
            for row in rows:
                loss = self._loss_fn(*row)
                self._host_sum += float(jnp.sum(
                    loss._data.astype(jnp.float32)))
                self._count += int(loss._data.size)

    def _folded_eval(self, nds):
        kw = None
        if self._k > 1:
            if nds[0].ndim < 2:
                raise ValueError(
                    f"fold_eval(k={self._k}) expects stacked [k, batch, "
                    "...] windows (pipeline.stage_window(k)); got shape "
                    f"{tuple(nds[0].shape)}")
            kw = int(nds[0].shape[0])
            if any(int(nd.shape[0]) != kw for nd in nds):
                raise ValueError(
                    "window leading dims disagree: "
                    f"{[tuple(nd.shape) for nd in nds]}")
        raws = [_raw(nd) for nd in nds]
        batch_sig = tuple((tuple(a.shape), str(a.dtype)) for a in raws)
        key_sig = (batch_sig, kw)
        entry = self._cache.get(key_sig)
        fresh = entry is None
        if fresh:
            try:
                entry = self._build(raws, kw)
            except Exception as e:
                self._note_fallback(f"capture failed: {e!r:.200}")
                return self._eager_eval(nds)
            self._cache[key_sig] = entry
        acc = self._acc
        if acc is None:
            acc = jnp.zeros((), jnp.float32)
        param_arrs = [_raw(p._data) for p in entry["params"]]
        tc = _perf() if fresh else None
        t0 = _perf() if _profiler._active else None
        try:
            try:
                new_acc = entry["fn"](acc, param_arrs, *raws)
            except Exception as e:
                _profiler.maybe_oom_postmortem(e, "gluon.fold_eval")
                raise
            self._acc = new_acc
            self._count += entry["loss_size"] * (kw or 1)
            if tc is not None:
                # every eval build is a DECLARED warmup: one program per
                # batch signature, typically compiled after the train
                # guard armed — register it, don't judge it
                with _profiler.compile_guard_paused():
                    _profiler.record_compile(
                        "gluon.fold_eval", self._compile_sig(entry, raws),
                        (_perf() - tc) * 1e3)
            if t0 is not None:
                _profiler.record_span(
                    "trainer.fold_eval", "trainer", t0,
                    args={"params": len(entry["params"]),
                          "k": int(kw or 1)})
            _profiler.incr("fold_eval_call")
        finally:
            _profiler.step_boundary()
        if not self._guard_armed:
            self._guard_armed = True
            _profiler.arm_compile_guard("gluon.fold_eval")

    def result(self, reset=True):
        """Mean loss over every element accumulated since the last reset —
        THE one host read of an eval pass."""
        total = self._host_sum
        if self._acc is not None:
            total += float(self._acc)
        count = self._count
        if reset:
            self._acc = None
            self._host_sum = 0.0
            self._count = 0
        return total / max(1, count)

    def _build(self, raws, kw):
        tr = self._trainer
        params = [p for p in tr._params if p._data is not None]
        loss_fn = self._loss_fn
        loss_cell = []

        def one_eval(param_arrs, batch):
            # a fixed key: eval is deterministic (dropout is identity
            # under is_training=False; the key only seeds the ceremony)
            key = jax.random.PRNGKey(0)
            with trace_scope(params, list(param_arrs), key, False):
                loss = loss_fn(*[NDArray(b) for b in batch])
            loss_data = loss._data
            if not loss_cell:
                loss_cell.append(int(_np.prod(loss_data.shape)))
            return jnp.sum(loss_data.astype(jnp.float32))

        if kw is None:
            def pure_eval(acc, param_arrs, *batch):
                return acc + one_eval(param_arrs, batch)
        else:
            def pure_eval(acc, param_arrs, *windows):
                def body(carry, xs):
                    return carry + one_eval(param_arrs, xs), None

                acc2, _ = jax.lax.scan(body, acc, tuple(windows))
                return acc2

        abstract = (
            jax.ShapeDtypeStruct((), jnp.float32),
            [jax.ShapeDtypeStruct(p._data.shape, p._data.dtype)
             for p in params],
            *[jax.ShapeDtypeStruct(a.shape, a.dtype) for a in raws],
        )
        jax.eval_shape(pure_eval, *abstract)
        # nothing donated: eval must never consume the live Parameters,
        # and the tiny acc register isn't worth a donation aliasing rule
        fn = jax.jit(pure_eval)
        return {"fn": fn, "params": params, "k": kw,
                "loss_size": loss_cell[0] if loss_cell else 1,
                "abstract": abstract}

    def _compile_sig(self, entry, raws):
        sig = {"__program__": f"fold_eval[{entry.get('k') or 1}]",
               "params": _profiler.sig_static(len(entry["params"]))}
        for i, a in enumerate(raws):
            sig[f"in{i}"] = {"k": "array", "shape": tuple(a.shape),
                             "dtype": str(a.dtype)}
        return sig


# ---------------------------------------------------------------------------
# The MXNET_STEP_FOLD=1 fast path inside Trainer.step: fold the whole
# optimizer tail (every fused group) into ONE donated jitted dispatch.
# ---------------------------------------------------------------------------

_TAIL_JITS = {}


def _tail_fn(plan_key, steps, donate):
    fn = _TAIL_JITS.get((plan_key, donate))
    if fn is None:
        def body(weights, grads, states, lrs, wds, ts, scalars):
            new_w = []
            new_s = []
            for g, step in enumerate(steps):
                gw, gs = [], []
                for m in range(len(weights[g])):
                    nw, ns = step(weights[g][m], grads[g][m], states[g][m],
                                  lrs[g][m], wds[g][m], ts[g][m], scalars)
                    gw.append(nw)
                    gs.append(list(ns))
                new_w.append(gw)
                new_s.append(gs)
            return new_w, new_s

        fn = jax.jit(body, donate_argnums=(0, 2) if donate else ())
        _TAIL_JITS[(plan_key, donate)] = fn
        while len(_TAIL_JITS) > 64:
            _TAIL_JITS.pop(next(iter(_TAIL_JITS)))
    return fn


def fold_update(optimizer, items, states):
    """Folded optimizer tail — :func:`optimizer.fused.fused_update`'s
    drop-in twin that updates EVERY fused group in one donated jitted
    dispatch instead of one ``group_apply`` per group (the
    ``MXNET_STEP_FOLD=1`` fast path inside ``Trainer.step``).  Returns the
    leftover per-tensor items, exactly like ``fused_update``."""
    agg = int(getattr(optimizer, "aggregate_num", 0) or 0)
    if agg <= 1 or not items or _engine.is_naive():
        return items
    groups, rest = _fused.plan_groups(optimizer, items, states)
    if not groups:
        return rest
    # bump ALL counts first, then read lr/wd/t (fused_update discipline)
    for members in groups.values():
        for i, _, _, _ in members:
            optimizer._update_count(i)
    ws, gs, sts, lrs, wds, ts, flats = [], [], [], [], [], [], []
    steps = []
    plan_key_parts = []
    for (step, dt, cx), members in groups.items():
        steps.append(step)
        plan_key_parts.append((step, len(members)))
        ws.append([_fused._concrete(w) for _, w, _, _ in members])
        gs.append([_fused._concrete(g) for _, _, g, _ in members])
        sts.append([[_fused._concrete(s) for s in flat]
                    for _, _, _, flat in members])
        lrs.append(jnp.asarray([optimizer._get_lr(i)
                                for i, _, _, _ in members], jnp.float32))
        wds.append(jnp.asarray([optimizer._get_wd(i)
                                for i, _, _, _ in members], jnp.float32))
        ts.append(jnp.asarray([optimizer._index_update_count[i]
                               for i, _, _, _ in members], jnp.float32))
        flats.append([flat for _, _, _, flat in members])
    scalars = {k: jnp.asarray(v, jnp.float32)
               for k, v in _fused._scalars(optimizer).items()}
    donate = _fused.donation_enabled()
    fn = _tail_fn(tuple(plan_key_parts), tuple(steps), donate)
    n_params = sum(len(m) for m in ws)
    n0 = _profiler.jit_cache_size(fn)
    tc = _perf()
    t0 = tc if _profiler._active else None
    guard_err = None
    try:
        new_w, new_s = fn(ws, gs, sts, lrs, wds, ts, scalars)
    except Exception as e:
        _profiler.maybe_oom_postmortem(e, "gluon.step_fold")
        raise
    compiled = n0 >= 0 and _profiler.jit_cache_size(fn) > n0
    if compiled:
        sig = {"__program__": "update_tail",
               "groups": _profiler.sig_static(
                   [(getattr(s, "__name__", "?"), n)
                    for s, n in plan_key_parts])}
        k = 0
        for grp in ws:
            for w in grp:
                sig[f"w{k}"] = {"k": "array", "shape": tuple(w.shape),
                                "dtype": str(w.dtype)}
                k += 1
        try:
            _profiler.record_compile("gluon.step_fold", sig,
                                     (_perf() - tc) * 1e3)
        except _profiler.CompileGuardError as e:
            guard_err = e   # buffers are donated: wire first, raise after
    for g, members in enumerate(groups.values()):
        for m, (_, w, _, _) in enumerate(members):
            _swap(w, new_w[g][m])
            for s_nd, s_new in zip(flats[g][m], new_s[g][m]):
                _swap(s_nd, s_new)
    if t0 is not None:
        _profiler.record_span("fused.group_apply", "optimizer", t0,
                              args={"params": n_params,
                                    "groups": len(groups), "folded": True})
    _profiler.incr("fused_step_call")
    _profiler.incr("fused_step_params", n_params)
    if guard_err is not None:
        raise guard_err
    if rest:
        _profiler.incr("fused_step_fallback_params", len(rest))
    return rest
