"""SPMDTrainer — the fully-fused TPU training path.

Parity map (SURVEY.md §3.2): in the reference, one training step is
CachedOp::Forward + Imperative::Backward + KVStore pushpull + a fused
optimizer op per parameter — four engine round-trips per step, with
cross-device communication handled by comm.h/NCCL/ps-lite.  Here the whole
step is ONE ``jax.jit``-compiled SPMD program over a named mesh:

    loss, grads = value_and_grad(forward ∘ loss)        # the tape
    new_params  = optimizer kernels (same registry as Trainer)
    collectives = inserted by XLA from sharding annotations (dp → grad
                  psum, tp → activation all-gather/reduce-scatter, ...)

Parameters and optimizer state are donated (static_alloc analog), so
steady-state HBM holds one copy.  The Gluon ``Trainer`` remains the
imperative-parity path; SPMDTrainer is the performance path the benchmarks
use — same Block, same loss, same Optimizer subclass.
"""
from __future__ import annotations

from time import perf_counter as _perf

import jax
import jax.numpy as jnp
import numpy as _np

from .. import profiler as _profiler
from . import elastic as _elastic
from .. import autograd
from .. import optimizer as opt_mod
from ..ndarray.ndarray import NDArray
from ..random import get_key, seed_epoch, push_traced_key, pop_traced_key
from ..gluon.block import _tls as _block_tls
from ..gluon.parameter import ParameterDict
from .mesh import current_mesh, local_mesh, mesh_scope
from .sharding import ShardingRules, default_rules, batch_pspec, param_sharding
from jax.sharding import NamedSharding, PartitionSpec as P

__all__ = ["SPMDTrainer"]


def _phase(name):
    """``jax.named_scope("spmd.<name>")``: the phase names every compiled
    step build carries (forward, loss, grad_sync, optimizer; the backward
    pass names itself ``transpose(jvp(spmd.forward))``).  Metadata only:
    the optimized program and its compile-cache key do not change."""
    return jax.named_scope("spmd." + name)


def _hlo_text_thunk(fn, call_args):
    """``() -> optimized HLO text`` of jitted ``fn`` as called with
    ``call_args``, for ``profiler.compiled_text``: holds a weak reference
    to ``fn`` and the ABSTRACT signature (shape, dtype, and the sharding of
    committed arrays), so neither buffers nor the trainer are kept alive."""
    import weakref

    abstract = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype,
            sharding=a.sharding if getattr(a, "committed", False) else None),
        call_args)
    ref = weakref.ref(fn)

    def text():
        f = ref()
        return None if f is None else f.lower(*abstract).compile().as_text()

    return text


class _EveryKey(dict):
    """dict that answers ``t`` for every key — feeds the traced update count
    into optimizer kernels (Adam/LAMB bias correction) without retracing."""

    def __init__(self, t):
        super().__init__()
        self._t = t

    def __contains__(self, k):
        return True

    def __getitem__(self, k):
        return self._t

    def __setitem__(self, k, v):
        pass


def _state_to_arrays(st):
    if st is None:
        return None
    if isinstance(st, NDArray):
        return st._data
    if isinstance(st, (list, tuple)):
        return tuple(_state_to_arrays(s) for s in st)
    return st


def _state_to_ndarrays(st):
    if st is None:
        return None
    if isinstance(st, (jnp.ndarray, jax.Array)) or hasattr(st, "dtype"):
        return NDArray(st)
    if isinstance(st, (list, tuple)):
        return tuple(_state_to_ndarrays(s) for s in st)
    return st


def _moe_extras(metrics, counters=None):
    """Frame metrics and counters → the step's extras: a map from a name to
    a raw jax scalar.  A routing metric ``m`` goes out as ``moe_<m>``, a
    layer's counter under the profiler counter's own name (the extras pytree
    is part of the compile signature: the layers of one build register the
    same names in every trace)."""
    def raw(v):
        return v._data if isinstance(v, NDArray) else v

    out = {f"moe_{name}": raw(v) for name, v in (metrics or {}).items()}
    out.update((name, raw(v)) for name, v in (counters or {}).items())
    return out


def _reduce_moe_extras(extras, axes):
    """The shards' routing metrics as the global-batch build reports them:
    counts summed, the load extremes taken over the shards."""
    ops = {"moe_expert_load_min": jax.lax.pmin,
           "moe_expert_load_max": jax.lax.pmax}
    return {k: ops.get(k, jax.lax.psum)(v, axes) for k, v in extras.items()}


def _release_pipeline_observers(name):
    """weakref.finalize hook: a collected pipelined trainer's gauges and
    slow-step annotator leave the export surfaces."""
    _profiler.unregister_metrics_provider(name)
    _profiler.unregister_slow_step_annotator(name)


def _release_spmd_memory(param_bytes, state_bytes):
    """weakref.finalize hook: a collected trainer's donated buffers leave
    the device-memory ledger (no self reference — the finalizer must not
    keep the trainer alive)."""
    _profiler.track_memory("spmd.params", "params").free(param_bytes)
    _profiler.track_memory("spmd.optimizer_state",
                           "optimizer_state").free(state_bytes)


def _release_comm_memory(nbytes):
    """weakref.finalize hook: a collected trainer's error-feedback
    residual buffers leave the ledger."""
    _profiler.track_memory("spmd.comm_residual", "comms").free(nbytes)


class SPMDTrainer:
    """Compile a Gluon block + loss + optimizer into one sharded train step.

    Parameters
    ----------
    block : gluon.Block
        Initialized model (``block.initialize()`` already called, possibly
        warmed once for deferred shapes).
    loss_fn : callable(outputs, label) -> NDArray
        Per-sample loss (a ``gluon.loss`` Block or any NDArray function).
    optimizer : str or Optimizer
    mesh : jax.sharding.Mesh, optional
        Defaults to the ambient ``mesh_scope`` or a pure-dp local mesh.
    rules : ShardingRules, optional
        Parameter placement (tp/fsdp).  Default: replicate (pure dp).
    sp_axis : int, optional
        Input axis to shard over 'sp' (sequence/context parallelism).
    stages : list of Blocks, optional
        A stage partition of ``block`` (``net.split_stages([...])`` or any
        list of Blocks whose parameters partition the model's).  Turns the
        step into a microbatched pipeline: forward AND backward slots run
        per the configured schedule inside the SAME single jitted program
        (``parallel/schedule.py``), with gradient allreduce still derived
        by XLA from the dp sharding — overlapped against the backward
        slots by the scheduler.
    pipeline : dict, optional (requires ``stages``)
        ``n_microbatches`` (required), ``schedule`` ("1f1b" default |
        "gpipe"), ``remat`` (bool or per-stage list; defaults True for
        gpipe — the GPipe paper's configuration — and False for 1f1b).
    compression : str or comm.CompressionPolicy, optional
        Gradient-compression tier for the dp-axis gradient exchange
        (docs/gradient_compression.md): "bf16" or "int8" (or a full
        policy).  Default: the ``MXNET_GRAD_COMPRESS`` env tier.  When
        active (pure-dp runs only — pipelined/sharded/sp builds fall
        back with a warning), the step's forward/backward runs per dp
        shard inside one shard_map and the fp32 gradient psum XLA would
        insert is replaced in-program by quantize → integer psum with
        per-block scale max-reduction → dequantize; opted-out parameter
        groups (norms/embeddings — ``optimizer.fused.
        quantization_sensitive``) keep an exact fp32 psum.  Error
        feedback residuals are donated step state, persisted through
        ``save_states``/``load_states``.
    """

    def __init__(
        self,
        block,
        loss_fn,
        optimizer,
        optimizer_params=None,
        mesh=None,
        rules: ShardingRules | None = None,
        sp_axis: int | None = None,
        stages=None,
        pipeline=None,
        compression=None,
    ):
        self._block = block
        self._loss_fn = loss_fn
        if isinstance(optimizer, opt_mod.Optimizer):
            self._optimizer = optimizer
        else:
            self._optimizer = opt_mod.create(optimizer, **(optimizer_params or {}))
        self._mesh = mesh or current_mesh() or local_mesh()
        self._rules = rules or default_rules()
        self._sp_axis = sp_axis

        params = block.collect_params()
        if isinstance(params, (dict, ParameterDict)):
            params = list(params.values())
        params.sort(key=lambda p: p.name)
        for p in params:
            if p._data is None:
                raise ValueError(
                    f"Parameter {p.name} is not materialized (deferred init?). "
                    "Run one eager forward pass before building SPMDTrainer."
                )
        self._params = params
        self._trainable_idx = [i for i, p in enumerate(params) if p.grad_req != "null"]
        self._optimizer.param_dict = {i: params[i] for i in self._trainable_idx}

        # Materialize param arrays on the mesh with their rule shardings.
        self._param_shardings = [
            param_sharding(self._mesh, p.name, p.shape, self._rules) for p in params
        ]
        # device_put via a host copy: putting a device-resident array onto a
        # mesh that CONTAINS its device can alias the source buffer, and the
        # first donated step would then kill the Parameter's own data
        # (breaking any later eager use of the block)
        self._param_arrays = [
            jax.device_put(_np.asarray(p._data._data), s)
            for p, s in zip(params, self._param_shardings)
        ]
        # Optimizer state: same sharding as its parameter (ZeRO comes from
        # the parameter rule; state simply follows).
        self._opt_states = []
        self._state_shardings = []
        for i in self._trainable_idx:
            st = self._optimizer.create_state_multi_precision(i, params[i].data())
            arrs = _state_to_arrays(st)
            shard = jax.tree_util.tree_map(
                lambda a: self._sharding_like(a, self._param_shardings[i]), arrs
            )
            arrs = jax.tree_util.tree_map(
                lambda a, s: jax.device_put(_np.asarray(a), s), arrs, shard)
            self._opt_states.append(arrs)
            self._state_shardings.append(shard)

        self._t = self._optimizer.begin_num_update
        # step t's PRNG key is fold_in(base key, t), derived INSIDE the
        # compiled step (_jit_wrapped); the base key is drawn once from
        # get_key() and again after mx.random.seed (_step_key)
        self._base_key = None
        self._key_epoch = None
        self._step_cache = {}
        self._guard_armed = False   # steady-state compile guard armed after
                                    # the first compiled step completes
        # device-memory ledger: the trainer owns its donated param/state
        # copies outright (donation keeps sizes constant, so these totals
        # are exact for the process lifetime); freed when the trainer is
        # collected
        import weakref as _weakref
        pb = sum(int(a.nbytes) for a in self._param_arrays)
        sb = sum(int(leaf.nbytes)
                 for st in self._opt_states
                 for leaf in jax.tree_util.tree_leaves(st))
        _profiler.track_memory("spmd.params", "params").alloc(pb)
        _profiler.track_memory("spmd.optimizer_state",
                               "optimizer_state").alloc(sb)
        self._mem_finalizer = _weakref.finalize(
            self, _release_spmd_memory, pb, sb)
        self._setup_pipeline(stages, pipeline)
        self._setup_compression(compression)
        from ..base import register_jit_cache_owner
        register_jit_cache_owner(self)
        if jax.process_count() > 1:
            # pin the rank for trace/metrics metadata: a multi-process SPMD
            # run may never touch a kvstore (collectives come from XLA), so
            # the trainer is the bootstrap point for this tier
            _profiler.set_process_info(rank=jax.process_index())

    def _invalidate_jit_cache(self):
        self._step_cache.clear()

    # ------------------------------------------------------------------
    def _setup_pipeline(self, stages, pipeline):
        """Validate the stage partition and freeze the schedule config +
        its static bubble accounting (the unit-cost simulation: tf=1,
        tb=2 — recompute slots add tf per the remat flags)."""
        import threading as _threading

        self._stages = list(stages) if stages else None
        self._moe_last = {}
        self._moe_pending = None
        self._moe_lock = _threading.Lock()   # step thread vs scrape thread
        self._moe_provider_name = None
        if self._stages is None:
            if pipeline:
                raise ValueError("pipeline= requires stages=")
            return
        from . import schedule as sched_mod

        self._sched_mod = sched_mod
        cfg = dict(pipeline or {})
        self._pipe_schedule = str(cfg.pop("schedule", "1f1b")).lower()
        self._pipe_micro = int(cfg.pop("n_microbatches", 0) or 0)
        default_remat = self._pipe_schedule == "gpipe"
        self._pipe_remat = cfg.pop("remat", default_remat)
        if cfg:
            raise ValueError(f"unknown pipeline config keys: {sorted(cfg)}")
        if self._pipe_micro < 1:
            raise ValueError("pipeline= needs n_microbatches >= 1")
        P = len(self._stages)
        idx_of = {id(p): i for i, p in enumerate(self._params)}
        self._stage_param_objs = []
        self._stage_param_idx = []
        seen = {}
        for s, st in enumerate(self._stages):
            ps = st.collect_params()
            if isinstance(ps, (dict, ParameterDict)):
                ps = list(ps.values())
            ps.sort(key=lambda p: p.name)
            idxs = []
            for p in ps:
                j = idx_of.get(id(p))
                if j is None:
                    raise ValueError(
                        f"stage {s} parameter {p.name} is not a parameter "
                        "of the trainer's block")
                if j in seen:
                    raise ValueError(
                        f"parameter {p.name} appears in stages {seen[j]} "
                        f"and {s}; stages must partition the parameters")
                seen[j] = s
                idxs.append(j)
            self._stage_param_objs.append(ps)
            self._stage_param_idx.append(idxs)
        missing = [self._params[j].name
                   for j in self._trainable_idx if j not in seen]
        if missing:
            raise ValueError(
                f"trainable parameters not covered by any stage: {missing}")
        self._pipe_sim = sched_mod.simulate_schedule(
            P, self._pipe_micro, self._pipe_schedule,
            tf=1.0, tb=2.0, remat=self._pipe_remat)
        # per-stage modeled windows (fractions of the simulated makespan):
        # scaled by each real step's wall time for spans/gauges
        total = self._pipe_sim["total"] or 1.0
        spans = []
        for s in range(P):
            slots = [t for t in self._pipe_sim["timeline"] if t[0] == s]
            spans.append((min(t[3] for t in slots) / total,
                          max(t[4] for t in slots) / total,
                          self._pipe_sim["per_stage_busy"][s] / total))
        self._pipe_stage_frac = spans
        self._pipe_last = {}
        self._pipe_last_step = None   # step id of this trainer's last
                                      # dispatch (slow-step attribution
                                      # stays scoped to OUR steps)
        self._register_pipeline_observers()

    def _register_pipeline_observers(self):
        """Metrics provider + slow-step annotator, holding the trainer
        only weakly (a provider closure owning ``self`` would pin the
        donated buffers past the trainer's lifetime)."""
        import weakref as _weakref

        ref = _weakref.ref(self)

        def provider():
            tr = ref()
            if tr is None:
                return {}
            tr._drain_moe_extras()
            out = {
                "stages": len(tr._stages),
                "microbatches": tr._pipe_micro,
                "bubble_fraction": round(
                    tr._pipe_sim["bubble_fraction"], 4),
            }
            out.update(tr._pipe_last)
            return out

        def annotator(stats):
            tr = ref()
            if tr is None or not tr._pipe_last:
                return None
            if stats.get("step") != tr._pipe_last_step:
                # a slow step this trainer did not dispatch (another
                # trainer's loop, or a not-yet-collected stale trainer):
                # its stage attribution would be fiction — stay silent
                return None
            busy = {int(k[len("stage"):-len("_busy_ms")]): v
                    for k, v in tr._pipe_last.items()
                    if k.startswith("stage") and k.endswith("_busy_ms")}
            if not busy:
                return None
            worst = max(busy, key=busy.get)
            return (f"stage {worst} modeled busy {busy[worst]:.1f} ms of "
                    f"{stats.get('wall_ms', 0.0):.1f} ms wall (schedule "
                    f"{tr._pipe_schedule}, bubble "
                    f"{tr._pipe_sim['bubble_fraction']:.0%})")

        name = _profiler.register_metrics_provider_unique("pipeline", provider)
        self._pipe_provider_name = name
        _profiler.register_slow_step_annotator(name, annotator)
        self._obs_finalizer = _weakref.finalize(
            self, _release_pipeline_observers, name)

    # ------------------------------------------------------------------
    def _setup_compression(self, compression):
        """Resolve the gradient-compression policy and freeze the static
        layout of the quantized dp-allreduce: which trainable slots
        compress (concat offsets into ONE flat bucket) vs stay exact, the
        shard count, the per-step raw/wire byte sizes, and — under error
        feedback — the per-shard residual buffer (donated step state,
        sharded over the batch axes)."""
        import warnings as _warnings

        from ..comm import compression as comp_mod

        from ..comm import ring as ring_mod

        self._comm_cfg = None
        self._comm_state = None
        self._comm_sharding = None
        self._comm_span_args = None
        policy = comp_mod.resolve_policy(compression)
        if policy is None:
            return
        mesh = self._mesh
        shards = int(mesh.shape["dp"]) * int(mesh.shape["fsdp"])
        reasons = []
        if self._stages is not None:
            reasons.append("pipelined stages")
        if self._sp_axis is not None:
            reasons.append("sequence parallelism (sp_axis)")
        for ax in ("pp", "ep", "sp", "tp"):
            if int(mesh.shape.get(ax, 1)) > 1:
                reasons.append(f"mesh axis {ax!r} > 1")
        # sharded parameters compress through the hop machinery (quantized
        # reduce-scatter of grads + quantized all-gather of updated shards,
        # comm/ring.py) — supported for the fsdp layout this repo's rules
        # produce: axis 0 sharded over 'fsdp' alone.  Anything fancier
        # (non-0 dims, multi-axis specs) still falls back with a reason.
        shard_mode = False
        for s in self._param_shardings:
            for i, names in enumerate(s.spec):
                if names is None:
                    continue
                nt = (names,) if isinstance(names, str) else tuple(names)
                if i != 0 or nt != ("fsdp",):
                    reasons.append(
                        "unsupported sharded-parameter layout (compression "
                        "handles axis-0 sharding over 'fsdp')")
                    break
                shard_mode = True
            else:
                continue
            break
        if reasons:
            _warnings.warn(
                "gradient compression requested but unsupported for this "
                f"build ({', '.join(reasons)}); running uncompressed. The "
                "quantized dp-allreduce needs a pure data-parallel step "
                "(replicated or fsdp-sharded parameters, no pipeline/sp).",
                UserWarning)
            return
        if shards <= 1:
            return  # no shard boundary: nothing crosses a wire
        codec = policy.codec
        algo = policy.algo
        dp_size = int(mesh.shape["dp"])
        fsdp_size = int(mesh.shape["fsdp"])
        if shard_mode:
            # the fsdp form: compressed slots are the fp32, non-opted-out
            # trainables whose axis 0 is ACTUALLY sharded; everything else
            # (opt-outs, non-fp32, replicated-because-indivisible) travels
            # exact.  The bucket is laid out in RING-CHUNK order — segment
            # i is the concatenation of every compressed slot's shard i —
            # so the reduce-scatter hands each device exactly its shards.
            comp_slots, exact_slots, spans = [], [], []
            seg_off = 0
            for slot, j in enumerate(self._trainable_idx):
                a = self._param_arrays[j]
                spec = self._param_shardings[j].spec
                sharded = len(spec) > 0 and spec[0] is not None
                cdc = (policy.codec_for(self._params[j].name)
                       if str(a.dtype) == "float32" and sharded else None)
                if cdc is None:
                    exact_slots.append(slot)
                else:
                    shard_sz = int(a.size) // fsdp_size
                    spans.append((seg_off, shard_sz, tuple(a.shape)))
                    seg_off += shard_sz
                    comp_slots.append(slot)
            if not comp_slots:
                return  # nothing sharded compresses: plain build is exact
            seg = seg_off                  # per-device segment length
            off = seg * fsdp_size          # full bucket (ring-chunk order)
            n_exact = sum(
                int(self._param_arrays[self._trainable_idx[s]].size)
                for s in exact_slots)
            # logical payload accounting: the grad reduce-scatter and the
            # updated-shard all-gather each move one encoded bucket where
            # fp32 fsdp would have moved the raw one
            bytes_raw = 4 * (2 * off + n_exact)
            bytes_wire = 2 * int(codec.wire_nbytes(off)) + 4 * n_exact
            hops, bytes_hop = ring_mod.rs_ag_hop_plan(codec, off, fsdp_size)
            if dp_size > 1:
                h2, b2 = ring_mod.hop_plan(codec, off, dp_size)
                bytes_hop = ((hops * bytes_hop + h2 * b2) // (hops + h2)
                             if hops + h2 else 0)
                hops += h2
            self._comm_cfg = {
                "policy": policy, "codec": codec,
                "ef": policy.error_feedback, "algo": algo, "sharded": True,
                "shard_ax": "fsdp", "F": fsdp_size, "S": seg,
                "comp_slots": comp_slots, "exact_slots": exact_slots,
                "spans": spans, "n": off, "shards": shards,
                "bytes_raw": int(bytes_raw), "bytes_wire": int(bytes_wire),
                "hops": int(hops), "bytes_hop": int(bytes_hop),
            }
        else:
            comp_slots, exact_slots, spans = [], [], []
            off = 0
            for slot, j in enumerate(self._trainable_idx):
                a = self._param_arrays[j]
                cdc = (policy.codec_for(self._params[j].name)
                       if str(a.dtype) == "float32" else None)
                if cdc is None:
                    exact_slots.append(slot)
                else:
                    spans.append((off, int(a.size), tuple(a.shape)))
                    off += int(a.size)
                    comp_slots.append(slot)
            if not comp_slots:
                return  # every group opted out: plain build IS the exact one
            n_exact = sum(
                int(self._param_arrays[self._trainable_idx[s]].size)
                for s in exact_slots)
            bytes_raw = 4 * (off + n_exact)
            bytes_wire = int(codec.wire_nbytes(off)) + 4 * n_exact
            if algo == "ring":
                hops, bytes_hop = ring_mod.hop_plan_axes(
                    codec, off, [d for d in (dp_size, fsdp_size) if d > 1])
            else:
                hops, bytes_hop = 0, 0  # psum: one fused exchange, no hops
            self._comm_cfg = {
                "policy": policy, "codec": codec,
                "ef": policy.error_feedback, "algo": algo, "sharded": False,
                "comp_slots": comp_slots, "exact_slots": exact_slots,
                "spans": spans, "n": off, "shards": shards,
                "bytes_raw": int(bytes_raw), "bytes_wire": int(bytes_wire),
                "hops": int(hops), "bytes_hop": int(bytes_hop),
            }
        self._comm_span_args = {"bytes_raw": int(bytes_raw),
                                "bytes_wire": int(bytes_wire),
                                "codec": codec.id,
                                "algo": ("ring" if self._comm_cfg["sharded"]
                                         else algo),
                                "hops": self._comm_cfg["hops"],
                                "bytes_hop": self._comm_cfg["bytes_hop"]}
        if policy.error_feedback:
            import weakref as _weakref

            self._comm_sharding = NamedSharding(mesh, P(("dp", "fsdp")))
            self._comm_state = jax.device_put(
                jnp.zeros((shards, off), jnp.float32), self._comm_sharding)
            cb = int(self._comm_state.nbytes)
            _profiler.track_memory("spmd.comm_residual", "comms").alloc(cb)
            self._comm_mem_finalizer = _weakref.finalize(
                self, _release_comm_memory, cb)

    # ------------------------------------------------------------------
    def _sharding_like(self, arr, param_sh):
        spec = param_sh.spec
        fitted = []
        for i, d in enumerate(arr.shape):
            names = spec[i] if i < len(spec) else None
            fitted.append(names)
        return NamedSharding(self._mesh, P(*fitted))

    @property
    def mesh(self):
        return self._mesh

    @property
    def optimizer(self):
        return self._optimizer

    @property
    def num_update(self):
        return self._t

    def learning_rate(self):
        opt = self._optimizer
        if opt.lr_scheduler is not None:
            return float(opt.lr_scheduler(self._t))
        return float(opt.lr)

    # ------------------------------------------------------------------
    def shard_batch(self, *arrays):
        """Place host batch arrays on the mesh with (dp, fsdp)[, sp]
        sharding.  Accepts numpy or NDArray; returns jax.Arrays.  In
        multi-process runs each host passes its local shard."""
        return tuple(self._place(a, lambda nd: batch_pspec(nd, self._sp_axis))
                     for a in arrays)

    def _place(self, a, spec_of):
        """One array onto the mesh under ``spec_of(ndim)``.  An array already
        staged there passes through with zero host work (the
        io.DataPipeline fast path: batches arrive device-resident); a
        transfer is a ``spmd.shard_batch`` span, which bills the step's
        host bucket — a per-step transfer on the consumer thread is exactly
        the host-input wall the async infeed removes, and its absence is
        asserted in tests."""
        if isinstance(a, NDArray):
            a = a._data
        a = _np.asarray(a) if not isinstance(a, jax.Array) else a
        sharding = NamedSharding(self._mesh, spec_of(a.ndim))
        if isinstance(a, jax.Array) and a.sharding == sharding:
            return a
        with _profiler.span("spmd.shard_batch", "trainer",
                            {"bytes": int(a.nbytes)}):
            if jax.process_count() > 1:
                return jax.make_array_from_process_local_data(sharding, a)
            return jax.device_put(a, sharding)

    def _compile_sig(self, arrays, program):
        """Compile-registry signature for a step build: named batch inputs
        (the recompile-attribution targets) + the parameter count."""
        sig = {"__program__": program, "label": _profiler.sig_array(arrays[-1]),
               "params": _profiler.sig_static(len(self._params))}
        for i, a in enumerate(arrays[:-1]):
            sig[f"input{i}"] = _profiler.sig_array(a)
        return sig

    def _record_step_obs(self, extras, tw):
        """Host-side pipeline/MoE observability for one dispatched step:
        declared counters (always on, like every repo counter), the
        ``pipeline.step``/``pipeline.stage``/``moe.step`` trace spans, and
        the provider gauges.  Per-stage spans/gauges carry the SCHEDULE's
        modeled attribution (unit-cost slot windows scaled onto the
        host-observed step span) — on a virtual CPU mesh the wall clock
        serializes stages, so modeled windows are the honest per-stage
        story and are labeled as such in docs/pipeline_parallelism.md."""
        now = _perf()
        wall_ms = (now - tw) * 1e3
        if self._comm_cfg is not None:
            # static per-step payload sizes (the layout is frozen at
            # build): raw = the fp32 bytes the dp exchange would have
            # moved, wire = encoded payload (codes + scales + the exact
            # opt-out groups' fp32)
            from ..comm import compression as comp_mod

            comp_mod.account(self._comm_cfg["bytes_raw"],
                             self._comm_cfg["bytes_wire"])
            if self._comm_cfg["hops"]:
                _profiler.incr("comms_ring_hops", self._comm_cfg["hops"])
        if self._stages is not None:
            sim = self._pipe_sim
            _profiler.incr("pipeline_step")
            _profiler.incr("pipeline_microbatch", self._pipe_micro)
            bubble_ms = sim["bubble_fraction"] * wall_ms
            _profiler.incr("pipeline_bubble_ms", int(round(bubble_ms)))
            last = {"wall_ms": round(wall_ms, 3)}
            for s, (f0, f1, busy_frac) in enumerate(self._pipe_stage_frac):
                last[f"stage{s}_busy_ms"] = round(busy_frac * wall_ms, 3)
            self._pipe_last_step = _profiler.current_step()
            if _profiler._active:
                _profiler.record_span(
                    "pipeline.step", "trainer", tw, now,
                    args={"schedule": self._pipe_schedule,
                          "stages": len(self._stages),
                          "microbatches": self._pipe_micro,
                          "bubble_ms": round(bubble_ms, 3),
                          "bubble_fraction": round(sim["bubble_fraction"], 4)})
                span_s = (now - tw)
                for s, (f0, f1, busy_frac) in enumerate(self._pipe_stage_frac):
                    _profiler.record_span(
                        "pipeline.stage", "trainer",
                        tw + f0 * span_s, tw + f1 * span_s,
                        args={"stage": s,
                              "busy_ms": round(busy_frac * wall_ms, 3),
                              "modeled": True})
            self._pipe_last.update(last)
        self._drain_moe_extras()
        if extras:
            # stash raw device scalars; converted at the NEXT step (or a
            # metrics read) — an immediate np.asarray would block the
            # training thread on the whole step's device completion and
            # forfeit dispatch/compute overlap
            self._moe_pending = extras
            if self._moe_provider_name is None and self._stages is None:
                # unpipelined MoE trainer: the routing gauges still belong
                # on the metrics surfaces — register a provider on first
                # sight of MoE extras (weakly, like the pipeline one)
                import weakref as _weakref

                ref = _weakref.ref(self)

                def moe_provider():
                    tr = ref()
                    if tr is None:
                        return {}
                    tr._drain_moe_extras()
                    return tr._moe_last

                name = _profiler.register_metrics_provider_unique(
                    "moe", moe_provider)
                self._moe_provider_name = name
                self._moe_finalizer = _weakref.finalize(
                    self, _profiler.unregister_metrics_provider, name)
    def _drain_moe_extras(self):
        """Convert the PREVIOUS step's stashed MoE extras (by now the
        device has finished that step, so the read doesn't stall the
        loop): bump the drop counter, refresh the gauges, add every
        other extra to the declared counter of its name
        (``moe_rows_routed_here``, and what a layer registered by
        ``model_zoo.moe.register_side``), emit the ``moe.step`` marker.
        Also called from the metrics provider so a snapshot between
        steps sees current values."""
        with self._moe_lock:
            # swap-and-convert under the lock: the step thread and a
            # metrics-scrape thread both drain, and an unlocked swap
            # would let both see the same pending dict and double-bump
            # the monotone drop counter
            pending, self._moe_pending = self._moe_pending, None
            if not pending:
                return
            vals = {key: _np.asarray(v) for key, v in pending.items()}
        self._moe_last = {}
        dropped = lmin = lmax = None
        if "moe_tokens_dropped" in vals:     # a routed layer ran
            dropped = int(round(float(vals.pop("moe_tokens_dropped").sum())))
            lmin = float(vals.pop("moe_expert_load_min").min())
            lmax = float(vals.pop("moe_expert_load_max").max())
            if dropped:
                _profiler.incr("moe_tokens_dropped", dropped)
            self._moe_last = {
                "moe_tokens_dropped": dropped,
                "moe_expert_load_min": lmin,
                "moe_expert_load_max": lmax,
            }
        _profiler.incr("moe_step")
        # every other extra is a count a step under a declared counter's
        # name: moe_rows_routed_here, and whatever a layer registered
        # (model_zoo.moe.register_side)
        for name, value in vals.items():
            count = int(round(float(value.sum())))
            _profiler.incr(name, count)
            self._moe_last[name] = count
        if self._stages is not None:
            self._pipe_last.update(self._moe_last)
        if _profiler._active:
            now = _perf()
            _profiler.record_span(
                "moe.step", "trainer", now, now,
                args={"tokens_dropped": dropped,
                      "expert_load_min": lmin,
                      "expert_load_max": lmax})

    def _post_step(self):
        # the guard arms AFTER the first compiled step: everything later
        # is steady state — recompiles from here on are counted (and
        # escalated per MXNET_COMPILE_GUARD)
        if not self._guard_armed:
            self._guard_armed = True
            _profiler.arm_compile_guard("spmd.trainer")

    # ------------------------------------------------------------------
    def step(self, data, label, batch_size=None):
        """Run one fused train step; returns the scalar loss (NDArray).

        ``batch_size`` defaults to the global batch (axis 0 of data); grads
        are rescaled by 1/batch_size like ``Trainer.step``.

        ``spmd.step.enqueue`` is the jitted call (enqueue plus the wait for
        the donated buffers), ``spmd.step.obs`` the counters, gauges and
        ``step_boundary`` after it.
        """
        inputs = data if isinstance(data, (list, tuple)) else (data,)
        with self._step_span():
            arrays = self.shard_batch(*inputs, label)
            if batch_size is None:
                batch_size = arrays[0].shape[0]
            sig = tuple((a.shape, str(a.dtype)) for a in arrays)
            fn = self._step_cache.get(sig)
            fresh = fn is None
            if fresh:
                fn = self._build_step(arrays)
                self._step_cache[sig] = fn
            comm = self._comm_state is not None
            call_args = (*self._step_args(batch_size), self._param_arrays,
                         self._opt_states,
                         *((self._comm_state,) if comm else ()), *arrays)
            lowered = text = None
            if fresh:
                text = _hlo_text_thunk(fn, call_args)
                if _profiler.compile_cost_enabled():
                    try:  # AOT lowering for XLA cost accounting (opt-in: the
                        lowered = fn.lower(*call_args)  # real call compiles again)
                    except Exception:
                        lowered = None
            tw = _perf()
            # the fused step is one XLA program whose collectives block on
            # every peer — the watchdog turns a dead peer into a clean exit
            _elastic.watchdog_arm("spmd.step")
            extras, done = None, False
            try:
                try:
                    with _profiler.span("spmd.step.enqueue", "trainer"):
                        out = fn(*call_args)
                except Exception as e:
                    # the fused step is THE training-tier OOM choke point:
                    # a RESOURCE_EXHAUSTED here gets one postmortem naming
                    # the top ledger owners before it surfaces
                    _profiler.maybe_oom_postmortem(e, "spmd.step")
                    raise
                if comm:
                    self._comm_state = out[2]
                self._param_arrays, self._opt_states = out[0], out[1]
                loss, extras = out[-2:]
                if fresh:
                    _profiler.record_compile(
                        "spmd.step", self._compile_sig(arrays, "step"),
                        (_perf() - tw) * 1e3, lowered=lowered, text=text)
                done = True
            finally:
                with _profiler.span("spmd.step.obs", "trainer"):
                    try:
                        if done:
                            self._record_step_obs(extras, tw)
                    finally:
                        _elastic.watchdog_disarm()
                        _profiler.step_boundary()
            self._post_step()
        return NDArray(loss)

    def _step_span(self):
        """The root span of one ``step`` call: ``step=`` is the optimizer
        step it runs (the identifier shared with the device trace), and
        under gradient compression the payload args, the bytes the
        counters account (trace_report's comms table)."""
        args = {"step": self._t + 1}
        if self._comm_span_args:
            args.update(self._comm_span_args)
        return _profiler.span("spmd.step", "trainer", args)

    def _step_key(self):
        """The base key, replicated over the mesh and never donated: drawn
        from ``get_key()`` before the first step and again when the user
        called ``mx.random.seed`` since (an ``int`` compare a step)."""
        epoch = seed_epoch()
        if epoch != self._key_epoch:
            self._set_base_key(get_key(), epoch)
        return self._base_key

    def _set_base_key(self, key, epoch):
        self._base_key = jax.device_put(
            _np.asarray(key), NamedSharding(self._mesh, P()))
        self._key_epoch = epoch

    def _step_args(self, batch_size):
        """What the compiled step takes before the parameters (see
        ``_jit_wrapped``): the base key and three HOST values, so that
        making them dispatches no program and nothing travels from one
        chip to the others — the int32 num_update, the float32 lr and the
        float32 rescale.  (One packed float32 array was tried: slicing it
        in the program cost BERT-base's step 0.03 ms, PERF.md, PR 27.)"""
        with _profiler.span("spmd.step.args", "trainer"):
            self._t += 1
            self._optimizer.num_update = self._t
            rescale = self._optimizer.rescale_grad / batch_size
            return (self._step_key(),
                    _np.asarray(self._t, _np.int32),
                    _np.asarray(self.learning_rate(), _np.float32),
                    _np.asarray(rescale, _np.float32))

    # ------------------------------------------------------------------
    def _build_step(self, example_arrays):
        return self._jit_wrapped(self._build_pure(example_arrays))

    def _jit_wrapped(self, step_fn):
        """jit a (key, t, lr, rescale, params, states[, comm], *batch) step
        with param/state (and error-feedback residual) donation and the
        trainer's output shardings.  The compiled program takes
        ``_step_args``'s base key and int32 num_update (``steps``: the
        parameters' names are in the compiled text) in place of the first
        two and derives them itself: step ``t``'s key is ``fold_in(base
        key, t)``."""
        def step(base_key, steps, *rest):
            key = jax.random.fold_in(base_key, steps)
            # traced under the trainer's mesh: ops that have to place
            # themselves on it (the attention kernels) read it there
            with mesh_scope(self._mesh):
                return step_fn(key, steps.astype(jnp.float32), *rest)

        # the device trace names the program jit_<__name__>
        step.__name__ = step_fn.__name__
        comm = self._comm_state is not None
        out_shardings = [
            list(self._param_shardings),
            list(self._state_shardings),
            NamedSharding(self._mesh, P()),
            # extras: a (possibly empty) dict of replicated scalars — a
            # prefix-leaf sharding covers whatever structure the build
            # produced
            NamedSharding(self._mesh, P()),
        ]
        if comm:
            # (params, states, comm, loss, extras): the residual rides
            # between states and loss, sharded over the batch axes
            out_shardings.insert(2, self._comm_sharding)
        donate = (4, 5, 6) if comm else (4, 5)
        with self._mesh:
            return jax.jit(
                step, donate_argnums=donate,
                out_shardings=tuple(out_shardings)
            )

    def _build_pure(self, example_arrays):
        if self._stages is not None:
            return self._build_pure_pipeline(example_arrays)
        if self._comm_cfg is not None:
            if self._comm_cfg.get("sharded"):
                return self._build_pure_compressed_sharded(example_arrays)
            return self._build_pure_compressed(example_arrays)
        trainable_idx = self._trainable_idx
        n_inputs = len(example_arrays) - 1
        forward_loss, aux_idx_cell = self._forward_loss_builder(n_inputs)

        def pure_step(key, t, lr, rescale, param_arrs, opt_states, *batch):
            train_arrs = [param_arrs[j] for j in trainable_idx]
            (_, (aux_vals, loss_mean, extras)), grads = jax.value_and_grad(
                forward_loss, has_aux=True
            )(train_arrs, param_arrs, key, batch)
            new_full, new_states = self._traced_optimizer_apply(
                t, lr, rescale, param_arrs, opt_states, grads)
            # aux side effects (BatchNorm running stats) overwrite their
            # frozen params.
            for k, v in zip(aux_idx_cell[0] if aux_idx_cell else [], aux_vals):
                new_full[k] = v.astype(new_full[k].dtype)
            return new_full, new_states, loss_mean, extras

        return pure_step

    def _forward_loss_builder(self, n_inputs):
        """The traced forward+loss shared by the unpipelined builds (plain
        and quantized-collective): returns ``(forward_loss,
        aux_idx_cell)`` where ``forward_loss(train_arrs, full_arrs, key,
        batch)`` differentiates the loss SUM over whatever batch slice it
        is traced with."""
        block = self._block
        loss_fn = self._loss_fn
        params = self._params
        trainable_idx = self._trainable_idx
        aux_idx_cell = []

        def forward_loss(train_arrs, full_arrs, key, batch):
            full = list(full_arrs)
            for j, arr in zip(trainable_idx, train_arrs):
                full[j] = arr
            from ..gluon.block import trace_scope
            from ..gluon.model_zoo import moe as moe_mod
            with trace_scope(params, full, key, True) as collector:
                with moe_mod.moe_loss_frame() as moe_fr:
                    ins = [NDArray(b) for b in batch[:n_inputs]]
                    with _phase("forward"):
                        out = block(*ins)
                    label = NDArray(batch[n_inputs])
                    with _phase("loss"):
                        loss = loss_fn(out, label)
                # Differentiate the SUM (matching ``loss.backward()`` on a
                # vector loss: implicit ones head-grads); Trainer-parity
                # mean-reduction comes from rescale_grad = 1/batch_size.
                loss_data = loss._data.astype(jnp.float32)
                loss_scalar = jnp.sum(loss_data)
                loss_mean = jnp.mean(loss_data)
                # MoE auxiliary losses (load balance + router z) join
                # the differentiated scalar; routing metrics leave the
                # program as extras for host-side counters/gauges
                moe_side = moe_mod.frame_loss(moe_fr)
                if moe_side is not None:
                    if isinstance(moe_side, NDArray):
                        moe_side = moe_side._data
                    loss_scalar = loss_scalar + moe_side.astype(jnp.float32)
                extras = _moe_extras(moe_mod.frame_metrics(moe_fr),
                                     moe_mod.frame_counters(moe_fr))
            if not aux_idx_cell:
                idx_map = {id(p): i for i, p in enumerate(params)}
                aux_idx_cell.append([idx_map[id(p)] for p, _ in collector])
            aux_vals = tuple(
                v._data if isinstance(v, NDArray) else v for _, v in collector
            )
            return loss_scalar, (aux_vals, loss_mean, extras)

        return forward_loss, aux_idx_cell

    # ------------------------------------------------------------------
    def _build_pure_compressed(self, example_arrays):
        """The quantized-collective twin of the unpipelined ``_build_pure``
        (docs/gradient_compression.md): the forward/backward runs per dp
        shard inside ONE ``shard_map`` over the batch axes, so the fp32
        gradient psum XLA would derive from the shardings is replaced
        in-program by quantize → integer psum with per-block scale
        max-reduction → dequantize (``comm.traced_allreduce``), all
        fused into the same donated-buffer compiled step — zero
        steady-state recompiles under the PR 9 guard.  Opted-out
        parameter groups keep an exact fp32 ``lax.psum``.  Note the
        per-shard semantics shift this implies for batch statistics:
        BatchNorm aux updates see the LOCAL batch shard and are pmean'd
        — the multi-worker data-parallel convention, not the global-batch
        one the uncompressed single-program build computes."""
        from ..comm import compression as comp_mod

        cfg = self._comm_cfg
        codec, ef = cfg["codec"], cfg["ef"]
        algo = cfg["algo"]
        comp_slots, exact_slots = cfg["comp_slots"], cfg["exact_slots"]
        spans = cfg["spans"]
        trainable_idx = self._trainable_idx
        n_slots = len(trainable_idx)
        n_inputs = len(example_arrays) - 1
        forward_loss, aux_idx_cell = self._forward_loss_builder(n_inputs)
        mesh = self._mesh
        AX = ("dp", "fsdp")
        fsdp = int(mesh.shape["fsdp"])
        P0 = P()
        batch_specs = tuple(batch_pspec(a.ndim) for a in example_arrays)

        def core(train_arrs, full_arrs, key, residual, batch):
            # distinct PRNG stream per shard: stochastic layers
            # decorrelate like independent data-parallel workers
            d = jax.lax.axis_index("dp") * fsdp + jax.lax.axis_index("fsdp")
            key = jax.random.fold_in(key, d)
            (_, (aux_vals, loss_mean, extras)), grads = jax.value_and_grad(
                forward_loss, has_aux=True
            )(train_arrs, full_arrs, key, batch)
            new_grads = [None] * n_slots
            with _phase("grad_sync"):
                for s in exact_slots:
                    new_grads[s] = jax.lax.psum(grads[s], AX)
                flat = jnp.concatenate(
                    [grads[s].reshape(-1) for s in comp_slots])
                reduced, resid_out = comp_mod.traced_allreduce(
                    codec, flat, residual[0] if ef else None, AX, algo=algo)
                for (off, n, shape), s in zip(spans, comp_slots):
                    new_grads[s] = reduced[off:off + n].reshape(shape)
            # host-facing scalars reduce across shards, so every export
            # surface matches the global-batch build
            loss_mean = jax.lax.pmean(loss_mean, AX)
            aux_vals = tuple(jax.lax.pmean(a, AX) for a in aux_vals)
            extras = _reduce_moe_extras(extras, AX)
            new_resid = resid_out[None, :] if ef else None
            return tuple(new_grads), new_resid, loss_mean, aux_vals, extras

        if ef:
            def shard_body(train_arrs, full_arrs, key, residual, *batch):
                return core(train_arrs, full_arrs, key, residual, batch)
            in_specs = (P0, P0, P0, P(AX)) + batch_specs
            out_specs = (P0, P(AX), P0, P0, P0)
        else:
            def shard_body(train_arrs, full_arrs, key, *batch):
                g, _, l, a, e = core(train_arrs, full_arrs, key, None, batch)
                return g, l, a, e
            in_specs = (P0, P0, P0) + batch_specs
            out_specs = (P0, P0, P0, P0)

        def pure_step(key, t, lr, rescale, param_arrs, opt_states, *rest):
            if ef:
                comm_state, batch = rest[0], rest[1:]
            else:
                comm_state, batch = None, rest
            train_arrs = [param_arrs[j] for j in trainable_idx]
            # core sums the gradients itself: under check_vma=True the
            # cotangent of a replicated (P()) operand is ALREADY summed
            # over the mapped axes, and the step applied shards x the
            # gradient; nor can the checker see the ring's relay
            mapped = jax.shard_map(shard_body, mesh=mesh,
                                   in_specs=in_specs, out_specs=out_specs,
                                   check_vma=False)
            if ef:
                grads_t, new_comm, loss_mean, aux_vals, extras = mapped(
                    train_arrs, list(param_arrs), key, comm_state, *batch)
            else:
                grads_t, loss_mean, aux_vals, extras = mapped(
                    train_arrs, list(param_arrs), key, *batch)
            new_full, new_states = self._traced_optimizer_apply(
                t, lr, rescale, param_arrs, opt_states, list(grads_t))
            for k, v in zip(aux_idx_cell[0] if aux_idx_cell else [], aux_vals):
                new_full[k] = v.astype(new_full[k].dtype)
            if ef:
                return new_full, new_states, new_comm, loss_mean, extras
            return new_full, new_states, loss_mean, extras

        return pure_step

    # ------------------------------------------------------------------
    def _build_pure_compressed_sharded(self, example_arrays):
        """The fsdp twin of ``_build_pure_compressed`` — the ZeRO++-style
        form from docs/gradient_compression.md: parameters live sharded on
        axis 0 over 'fsdp'; inside ONE ``shard_map`` over the batch axes
        the compressed trainables are materialized for the forward by a
        QUANTIZED ring all-gather of the updated shards (one bucket in
        ring-chunk order: segment i = every slot's shard i concatenated),
        and their gradients leave via quantized ring allreduce over 'dp'
        followed by quantized ring reduce-scatter over 'fsdp' — so every
        inter-chip payload on both legs is the codec's encoded form.
        Exact slots (opt-outs, non-fp32, replicated-because-indivisible)
        ride fp32 ``all_gather``/``psum``/``psum_scatter``.  Error
        feedback accumulates r_dp + r_rs/|dp| per device in the full
        ring-chunk bucket, riding the same donated ``_comm_state`` rows.
        Gradients return with the parameter shardings, so the optimizer
        tail outside the shard_map partitions elementwise with zero
        comms."""
        from ..comm import ring as ring_mod

        cfg = self._comm_cfg
        codec, ef = cfg["codec"], cfg["ef"]
        comp_slots, exact_slots = cfg["comp_slots"], cfg["exact_slots"]
        spans = cfg["spans"]
        shard_ax, F, S = cfg["shard_ax"], cfg["F"], cfg["S"]
        trainable_idx = self._trainable_idx
        n_slots = len(trainable_idx)
        n_inputs = len(example_arrays) - 1
        forward_loss, aux_idx_cell = self._forward_loss_builder(n_inputs)
        mesh = self._mesh
        AX = ("dp", "fsdp")
        dp_size = int(mesh.shape["dp"])
        fsdp = int(mesh.shape["fsdp"])
        dp_axes = tuple(a for a in AX if a != shard_ax)
        param_specs = [s.spec for s in self._param_shardings]
        train_specs = [param_specs[j] for j in trainable_idx]

        def is_sharded(spec):
            return len(spec) > 0 and spec[0] is not None

        P0 = P()
        batch_specs = tuple(batch_pspec(a.ndim) for a in example_arrays)

        def gather_fp(x):
            return jax.lax.all_gather(x, shard_ax, axis=0, tiled=True)

        def core(train_arrs, full_arrs, key, residual, batch):
            d = jax.lax.axis_index("dp") * fsdp + jax.lax.axis_index("fsdp")
            key = jax.random.fold_in(key, d)
            # quantized all-gather of the updated shards: the bucket's
            # ring-chunk layout means one AG delivers every slot's full
            # parameter as F contiguous row-slices
            shard_bucket = jnp.concatenate(
                [train_arrs[s].reshape(-1) for s in comp_slots])
            full_bucket = ring_mod.ring_all_gather(
                codec, shard_bucket, shard_ax)
            seg2d = full_bucket.reshape(F, S)
            gathered = list(train_arrs)
            for (off, ssz, shape), s in zip(spans, comp_slots):
                gathered[s] = seg2d[:, off:off + ssz].reshape(shape)
            for s in exact_slots:
                if is_sharded(train_specs[s]):
                    gathered[s] = gather_fp(train_arrs[s])
            full = list(full_arrs)
            tset = set(trainable_idx)
            for j in range(len(full)):
                if j not in tset and is_sharded(param_specs[j]):
                    full[j] = gather_fp(full[j])
            (_, (aux_vals, loss_mean, extras)), grads = jax.value_and_grad(
                forward_loss, has_aux=True
            )(gathered, full, key, batch)
            new_grads = [None] * n_slots
            with _phase("grad_sync"):
                for s in exact_slots:
                    if is_sharded(train_specs[s]):
                        g = grads[s]
                        if dp_axes:
                            g = jax.lax.psum(g, dp_axes)
                        new_grads[s] = jax.lax.psum_scatter(
                            g, shard_ax, scatter_dimension=0, tiled=True)
                    else:
                        new_grads[s] = jax.lax.psum(grads[s], AX)
                # gradient bucket in the same ring-chunk order: row i of each
                # slot's (F, shard) view lands in segment i
                flat = jnp.concatenate(
                    [grads[s].reshape(F, -1) for s in comp_slots],
                    axis=1).reshape(-1)
                comp = flat + residual[0] if ef else flat
                if dp_size > 1:
                    x, r_dp = ring_mod.ring_allreduce(
                        codec, comp, None, dp_axes)
                else:
                    x, r_dp = comp, None
                shard_red, r_rs = ring_mod.ring_reduce_scatter(
                    codec, x, None, shard_ax)
                resid = r_rs if r_dp is None else r_dp + r_rs / dp_size
                for (off, ssz, shape), s in zip(spans, comp_slots):
                    new_grads[s] = shard_red[off:off + ssz].reshape(
                        (shape[0] // F,) + tuple(shape[1:]))
            loss_mean = jax.lax.pmean(loss_mean, AX)
            aux_vals = tuple(jax.lax.pmean(a, AX) for a in aux_vals)
            extras = _reduce_moe_extras(extras, AX)
            new_resid = resid[None, :] if ef else None
            return tuple(new_grads), new_resid, loss_mean, aux_vals, extras

        grad_specs = tuple(
            train_specs[s] if is_sharded(train_specs[s]) else P0
            for s in range(n_slots))
        tr_in = tuple(train_specs)
        full_in = tuple(param_specs)
        if ef:
            def shard_body(train_arrs, full_arrs, key, residual, *batch):
                return core(train_arrs, full_arrs, key, residual, batch)
            in_specs = (tr_in, full_in, P0, P(AX)) + batch_specs
            out_specs = (grad_specs, P(AX), P0, P0, P0)
        else:
            def shard_body(train_arrs, full_arrs, key, *batch):
                g, _, l, a, e = core(train_arrs, full_arrs, key, None, batch)
                return g, l, a, e
            in_specs = (tr_in, full_in, P0) + batch_specs
            out_specs = (grad_specs, P0, P0, P0)

        def pure_step(key, t, lr, rescale, param_arrs, opt_states, *rest):
            if ef:
                comm_state, batch = rest[0], rest[1:]
            else:
                comm_state, batch = None, rest
            train_arrs = tuple(param_arrs[j] for j in trainable_idx)
            mapped = jax.shard_map(shard_body, mesh=mesh,
                                   in_specs=in_specs, out_specs=out_specs,
                                   check_vma=False)
            if ef:
                grads_t, new_comm, loss_mean, aux_vals, extras = mapped(
                    train_arrs, tuple(param_arrs), key, comm_state, *batch)
            else:
                grads_t, loss_mean, aux_vals, extras = mapped(
                    train_arrs, tuple(param_arrs), key, *batch)
            new_full, new_states = self._traced_optimizer_apply(
                t, lr, rescale, param_arrs, opt_states, list(grads_t))
            for k, v in zip(aux_idx_cell[0] if aux_idx_cell else [], aux_vals):
                new_full[k] = v.astype(new_full[k].dtype)
            if ef:
                return new_full, new_states, new_comm, loss_mean, extras
            return new_full, new_states, loss_mean, extras

        return pure_step

    def _traced_optimizer_apply(self, t, lr, rescale, param_arrs, opt_states,
                                grads):
        """Optimizer tail of every step build (unpipelined AND pipelined):
        reuse the registered Optimizer's own update methods with traced
        t/lr — exact parity with the imperative Trainer.  ``grads`` aligns
        with ``self._trainable_idx``."""
        opt = self._optimizer
        save = (
            opt._index_update_count,
            opt.num_update,
            opt.lr,
            opt.lr_scheduler,
            opt.rescale_grad,
        )
        opt._index_update_count = _EveryKey(t)
        opt.num_update = t
        opt.lr = lr
        opt.lr_scheduler = None
        opt.rescale_grad = rescale
        # shadow the bookkeeping method: count is the traced t
        opt._update_count = lambda idx: None
        try:
            new_full = list(param_arrs)
            new_states = []
            with _phase("optimizer"):
                for slot, j in enumerate(self._trainable_idx):
                    w = NDArray(param_arrs[j])
                    g = NDArray(grads[slot])
                    st = _state_to_ndarrays(opt_states[slot])
                    opt.update_multi_precision(j, w, g, st)
                    new_full[j] = w._data
                    new_states.append(_state_to_arrays(st))
        finally:
            (
                opt._index_update_count,
                opt.num_update,
                opt.lr,
                opt.lr_scheduler,
                opt.rescale_grad,
            ) = save
            del opt._update_count  # restore the class method
        return new_full, new_states

    # ------------------------------------------------------------------
    def _build_pure_pipeline(self, example_arrays):
        """The pipelined twin of ``_build_pure``: the forward/backward is
        driven by the microbatch scheduler (``parallel/schedule.py``) —
        explicit F/B slots per the configured schedule, activation stashes
        handed between them, per-stage remat — followed by the SAME traced
        optimizer tail.  Still one pure function; ``_jit_wrapped`` turns
        it into one donated-buffer program, so the dp-axis gradient psum
        XLA derives from the shardings is free to overlap the remaining
        backward slots inside that single program."""
        stages = self._stages
        loss_fn = self._loss_fn
        params = self._params
        trainable_idx = self._trainable_idx
        stage_idx = self._stage_param_idx
        stage_objs = self._stage_param_objs
        P = len(stages)
        M = self._pipe_micro
        kind = self._pipe_schedule
        remat = self._pipe_remat
        sched_mod = self._sched_mod
        n_inputs = len(example_arrays) - 1
        aux_maps = [None] * P   # per stage: global param idx per aux slot
        from ..gluon.model_zoo import moe as moe_mod

        def pure_step(key, t, lr, rescale, param_arrs, opt_states, *batch):
            inputs = tuple(batch[:n_inputs])
            label = batch[n_inputs]

            def make_stage(s):
                block = stages[s]
                objs = stage_objs[s]

                def fn(st_arrs, h):
                    from ..gluon.block import trace_scope

                    # per-(stage, microbatch) PRNG: folding the stage alone
                    # would hand every microbatch the same dropout masks;
                    # the scheduler pins the slot around remat recomputes
                    # too, so the backward re-trace folds identically
                    slot = sched_mod.current_slot()
                    m_idx = 0 if slot is None else slot[1]
                    slot_key = jax.random.fold_in(
                        jax.random.fold_in(key, s), m_idx)
                    with trace_scope(objs, st_arrs, slot_key, True) \
                            as collector:
                        with moe_mod.moe_loss_frame() as fr:
                            ins = h if isinstance(h, tuple) else (h,)
                            with _phase("forward"):
                                out = block(*[NDArray(b) for b in ins])
                    side = moe_mod.frame_loss(fr)
                    if side is None:
                        side = jnp.zeros(())
                    else:
                        if isinstance(side, NDArray):
                            side = side._data
                        # per-microbatch aux losses average over M: the
                        # load-balance/z regularizers are mean-style — the
                        # batch split must not scale them
                        side = side.astype(jnp.float32) / M
                    moem = moe_mod.frame_metrics(fr)
                    moe_t = () if moem is None else (
                        moem["tokens_dropped"], moem["expert_load_min"],
                        moem["expert_load_max"])
                    if aux_maps[s] is None:
                        idx_map = {id(p): i for i, p in enumerate(params)}
                        aux_maps[s] = [idx_map[id(p)] for p, _ in collector]
                    aux_vals = tuple(
                        v._data if isinstance(v, NDArray) else v
                        for _, v in collector)
                    if isinstance(out, (list, tuple)):
                        h_out = tuple(o._data for o in out)
                    else:
                        h_out = out._data
                    return h_out, side, (aux_vals, moe_t)

                return fn

            loss_elems = [None]   # per-microbatch loss element count
                                  # (static: same shape every microbatch)

            def loss_slot(h, lab):
                # last-stage loss: same ceremony, no stage params
                slot = sched_mod.current_slot()
                m_idx = 0 if slot is None else slot[1]
                push_traced_key(jax.random.fold_in(
                    jax.random.fold_in(key, P), m_idx))
                prev = getattr(_block_tls, "tracing", 0)
                _block_tls.tracing = prev + 1
                try:
                    with autograd._scope(False, True), _phase("loss"):
                        if isinstance(h, tuple):
                            out = [NDArray(o) for o in h]
                        else:
                            out = NDArray(h)
                        loss = loss_fn(out, NDArray(lab))
                finally:
                    _block_tls.tracing = prev
                    pop_traced_key()
                loss_data = loss._data.astype(jnp.float32)
                loss_elems[0] = int(loss_data.size)
                return jnp.sum(loss_data)

            task_sum, _side_sum, grads, metrics = sched_mod.pipeline_value_and_grad(
                [make_stage(s) for s in range(P)], loss_slot,
                [[param_arrs[j] for j in stage_idx[s]] for s in range(P)],
                inputs, label, M, schedule=kind, remat=remat,
                stage_outputs="rich")

            full_grads = [None] * len(params)
            for s in range(P):
                for j, g in zip(stage_idx[s], grads[s]):
                    full_grads[j] = g
            grads_list = [
                full_grads[j] if full_grads[j] is not None
                else jnp.zeros_like(param_arrs[j])
                for j in trainable_idx
            ]
            new_full, new_states = self._traced_optimizer_apply(
                t, lr, rescale, param_arrs, opt_states, grads_list)

            # BatchNorm-style aux: average each stage's collected values
            # over its microbatches, then overwrite the frozen params
            for s in range(P):
                if not aux_maps[s]:
                    continue
                per_mb = [m[0] for m in metrics[s]]   # aux_vals tuples
                for slot, j in enumerate(aux_maps[s]):
                    mean = sum(vals[slot] for vals in per_mb) / M
                    new_full[j] = mean.astype(new_full[j].dtype)

            # MoE routing metrics: drops sum over (stage, microbatch),
            # loads min/max across them
            dropped = None
            lmin = None
            lmax = None
            for s in range(P):
                for m in metrics[s]:
                    if not m[1]:
                        continue
                    d, mn, mx = m[1]
                    dropped = d if dropped is None else dropped + d
                    lmin = mn if lmin is None else jnp.minimum(lmin, mn)
                    lmax = mx if lmax is None else jnp.maximum(lmax, mx)
            extras = {} if dropped is None else {
                "moe_tokens_dropped": dropped,
                "moe_expert_load_min": lmin,
                "moe_expert_load_max": lmax,
            }
            # mean over every loss ELEMENT (not per sample): exact parity
            # with the unpipelined jnp.mean for vector/matrix losses
            loss_mean = task_sum / (loss_elems[0] * M)
            return new_full, new_states, loss_mean, extras

        return pure_step

    # ------------------------------------------------------------------
    def sync_to_block(self):
        """Write the trainer-held (possibly sharded) arrays back into the
        Gluon Parameters — call before ``save_parameters`` or eager eval.
        Arrays are gathered off the mesh so eager ops don't mix
        single-device inputs with mesh-sharded weights."""
        with autograd.pause():
            for p, a in zip(self._params, self._param_arrays):
                p._data._data = jnp.asarray(_np.asarray(a))
                p._data._version += 1

    def _comm_local_np(self):
        """This process's rows of the sharded residual, in shard order.
        ``np.asarray`` on the full array would refuse a multi-process
        sharding (non-addressable devices); in single-process runs the
        addressable shards ARE the whole array."""
        shards = sorted(self._comm_state.addressable_shards,
                        key=lambda s: s.index[0].start or 0)
        return _np.concatenate([_np.asarray(s.data) for s in shards], axis=0)

    def save_states(self, fname):
        import pickle

        from ..checkpoint import atomic_write_bytes

        flat = jax.tree_util.tree_map(_np.asarray, self._opt_states)
        # the dropout stream is a function of (base key, num_update): with
        # both in the snapshot a resumed run draws the unbroken run's masks
        payload = {"states": flat, "num_update": self._t,
                   "base_key": _np.asarray(self._step_key())}
        if self._comm_state is not None:
            # error-feedback residuals are step state: dropping them at
            # restore re-injects one step's quantization error.  Each
            # process snapshots its OWN shard rows (per-host files, like
            # the reference's per-worker kvstore state)
            payload["comm_residual"] = self._comm_local_np()
            payload["comm_codec"] = self._comm_cfg["codec"].id
        # atomic (tmp + os.replace): preemption mid-write never tears it
        atomic_write_bytes(fname, pickle.dumps(payload))

    def load_states(self, fname):
        import pickle

        with open(fname, "rb") as f:
            payload = pickle.load(f)
        loaded = payload["states"]
        self._opt_states = jax.tree_util.tree_map(
            lambda a, s: jax.device_put(jnp.asarray(a), s),
            loaded,
            self._state_shardings,
        )
        self._t = payload["num_update"]
        if payload.get("base_key") is not None:
            self._set_base_key(payload["base_key"], seed_epoch())
        cr = payload.get("comm_residual")
        if self._comm_state is not None:
            # expected per-process shape from shard METADATA — snapshots
            # hold local rows, and materializing the residual just to
            # compare shapes would be a full D2H copy per restore
            local_rows = sum(int(s.data.shape[0])
                             for s in self._comm_state.addressable_shards)
            expect = (local_rows,) + tuple(self._comm_state.shape[1:])
            if (cr is not None
                    and payload.get("comm_codec") == self._comm_cfg["codec"].id
                    and tuple(cr.shape) == expect):
                if jax.process_count() > 1:
                    self._comm_state = jax.make_array_from_process_local_data(
                        self._comm_sharding, _np.asarray(cr))
                else:
                    self._comm_state = jax.device_put(
                        jnp.asarray(cr), self._comm_sharding)
            elif cr is None:
                # snapshot carries no residuals (saved uncompressed or
                # pre-compression): keeping this trainer's live ones would
                # feed post-checkpoint error into the restored trajectory
                self._comm_state = jax.device_put(
                    jnp.zeros_like(self._comm_state), self._comm_sharding)
            else:
                import warnings as _warnings

                _warnings.warn(
                    "snapshot error-feedback residuals don't match this "
                    "trainer's compression layout (codec or shard count "
                    "changed); starting from zero residuals", UserWarning)
                self._comm_state = jax.device_put(
                    jnp.zeros_like(self._comm_state), self._comm_sharding)
