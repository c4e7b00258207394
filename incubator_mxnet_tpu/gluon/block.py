"""Gluon Block / HybridBlock.

Parity target: [U:python/mxnet/gluon/block.py] + the CachedOp it drives
([U:src/imperative/cached_op.cc]).  THE central mapping of the whole build
(SURVEY.md §3.2): the reference's ``hybridize()`` traces ``hybrid_forward``
with symbols once and builds a CachedOp; here ``hybridize()`` compiles the
whole block tree into ONE ``jax.jit`` callable per input signature:

* the jitted function is pure: ``(prng_key, *inputs, *params) ->
  (*outputs, *aux_updates)``;
* during tracing, ``Parameter.data()`` returns traced stand-ins so child
  blocks compose into the same graph (the reference reaches the same goal
  by passing ``F=symbol`` down the tree);
* BatchNorm-style running-stat updates are collected as extra outputs and
  written back after execution (the reference mutates aux arrays inside
  the op);
* under ``autograd.record``, the whole jitted call is ONE tape node —
  exactly CachedOp's "one tape node for the whole cached graph";
* ``static_alloc`` maps to XLA buffer donation (donate_argnums on params is
  unsafe here because params persist; donation applies in the fused
  train-step path in parallel/), ``static_shape`` is implicit (XLA).
"""
from __future__ import annotations

import contextlib
import re
import threading

import jax
import numpy as _np

from time import perf_counter as _perf

from .. import autograd
from .. import ndarray as nd_mod
from .. import profiler as _profiler
from ..context import current_context
from ..engine import DeferredArray as _Deferred
from ..ndarray.ndarray import NDArray
from ..random import get_key, push_traced_key, pop_traced_key
from .parameter import Parameter, ParameterDict

__all__ = ["Block", "HybridBlock", "SymbolBlock", "name_scope",
           "trace_scope", "traced_params"]

_tls = threading.local()


def _naming_counter():
    if not hasattr(_tls, "counters"):
        _tls.counters = [{}]
    return _tls.counters[-1]


def _gen_prefix(hint):
    c = _naming_counter()
    idx = c.get(hint, 0)
    c[hint] = idx + 1
    return f"{hint}{idx}_"


@contextlib.contextmanager
def name_scope():
    if not hasattr(_tls, "counters"):
        _tls.counters = [{}]
    _tls.counters.append({})
    try:
        yield
    finally:
        _tls.counters.pop()


# -- aux-update collection (BatchNorm running stats under jit) --------------


def _aux_stack():
    if not hasattr(_tls, "aux"):
        _tls.aux = []
    return _tls.aux


def collect_aux_update(param, new_value):
    """Called by layers whose forward has aux side effects.  Inside a
    hybridize trace the update becomes an extra jit output; eagerly it is
    applied immediately."""
    stack = _aux_stack()
    if stack:
        stack[-1].append((param, new_value))
    else:
        with autograd.pause():
            param.set_data(new_value)


def _is_tracing():
    return bool(getattr(_tls, "tracing", 0))


@contextlib.contextmanager
def trace_scope(params, arrays, key, training, collector=None):
    """THE trace-scope ceremony shared by every whole-graph capturer in the
    repo — the CachedOp build (``_build_cache``), ``export_jittable``, the
    SPMDTrainer step builders (``parallel/trainer.py``) and the Gluon step
    fold (``step_fold.py``) all enter their traces through here, so the
    fragile save/restore protocol exists exactly once.

    For each ``(param, array)`` pair: sets ``param._traced_data`` so
    ``Parameter.data()`` returns the traced stand-in, pushes ``key`` as the
    traced PRNG key, pushes an aux-update frame (``collector`` or a fresh
    throwaway) so BatchNorm-style side effects are captured instead of
    applied, marks the block-tracing TLS, and enters recording-off autograd
    with the given ``training`` mode — restoring ALL of it on exit,
    exception or not.  Yields the aux frame."""
    saved = []
    for p, a in zip(params, arrays):
        saved.append(getattr(p, "_traced_data", None))
        p._traced_data = a if isinstance(a, NDArray) else NDArray(a)
    push_traced_key(key)
    own = collector if collector is not None else []
    _aux_stack().append(own)
    prev = getattr(_tls, "tracing", 0)
    _tls.tracing = prev + 1
    try:
        with autograd._scope(False, training):
            yield own
    finally:
        _tls.tracing = prev
        _aux_stack().pop()
        pop_traced_key()
        for p, s in zip(params, saved):
            p._traced_data = s


def traced_params(params, arrays):
    """Eval-mode :func:`trace_scope` with a fixed key — the ceremony for
    hand-built pure jit programs that call Gluon blocks with parameters
    BAKED IN as captured constants (the KV-cache decode discipline:
    per-leaf jit argument processing costs ~0.5 ms/arg on slow hosts, and
    inference params are frozen anyway).  Used by
    ``model_zoo.transformer._KVCacheDecoder`` and the serving tier's
    generation programs."""
    return trace_scope(params, arrays, jax.random.PRNGKey(0), False)


class _BlockScope:
    """Name-scope manager for Blocks (parity: ``_BlockScope`` in the
    reference — naming discipline matters for checkpoint compat)."""

    _current = threading.local()

    def __init__(self, block):
        self._block = block
        self._counter = {}
        self._old_scope = None

    @staticmethod
    def create(prefix, params, hint):
        current = getattr(_BlockScope._current, "value", None)
        if current is None:
            if prefix is None:
                prefix = _gen_prefix(hint)
            if params is None:
                params = ParameterDict(prefix)
            else:
                params = ParameterDict(params.prefix, params)
            return prefix, params
        if prefix is None:
            idx = current._counter.get(hint, 0)
            current._counter[hint] = idx + 1
            prefix = f"{hint}{idx}_"
        if params is None:
            parent = current._block.params
            params = ParameterDict(parent.prefix + prefix, parent._shared)
        else:
            params = ParameterDict(params.prefix, params)
        return current._block.prefix + prefix, params

    def __enter__(self):
        if self._block._empty_prefix:
            return self
        self._old_scope = getattr(_BlockScope._current, "value", None)
        _BlockScope._current.value = self
        return self

    def __exit__(self, *exc):
        if self._block._empty_prefix:
            return
        _BlockScope._current.value = self._old_scope


class Block:
    """Base building block (parity: ``gluon.Block``).  Define-by-run:
    ``__call__`` dispatches to ``forward`` with NDArrays."""

    def __init__(self, prefix=None, params=None):
        self._empty_prefix = prefix == ""
        self._prefix, self._params = _BlockScope.create(prefix, params, self._alias())
        self._name = self._prefix[:-1] if self._prefix.endswith("_") else self._prefix
        self._scope = _BlockScope(self)
        self._children = {}
        self._forward_hooks = []
        self._forward_pre_hooks = []

    def _alias(self):
        return self.__class__.__name__.lower()

    # -- attribute plumbing ---------------------------------------------
    def __setattr__(self, name, value):
        if isinstance(value, Block):
            existing = self.__dict__.get("_children")
            if existing is not None:
                existing[name] = value
        elif isinstance(value, Parameter):
            if "_params" in self.__dict__:
                self._params._params[value.name] = value
        super().__setattr__(name, value)

    @property
    def prefix(self):
        return self._prefix

    @property
    def name(self):
        return self._name

    def name_scope(self):
        return self._scope

    @property
    def params(self):
        return self._params

    def collect_params(self, select=None):
        """All Parameters of self and children, optionally regex-filtered
        (parity: ``Block.collect_params``)."""
        ret = ParameterDict(self._params.prefix)
        if select is None:
            ret.update(self.params)
        else:
            pattern = re.compile(select)
            ret.update({k: v for k, v in self.params.items() if pattern.match(k)})
        for child in self._children.values():
            ret.update(child.collect_params(select=select))
        return ret

    def register_child(self, block, name=None):
        self._children[name or str(len(self._children))] = block

    def register_forward_hook(self, hook):
        self._forward_hooks.append(hook)

    def register_forward_pre_hook(self, hook):
        self._forward_pre_hooks.append(hook)

    def apply(self, fn):
        for child in self._children.values():
            child.apply(fn)
        fn(self)
        return self

    def initialize(self, init=None, ctx=None, verbose=False, force_reinit=False):
        self.collect_params().initialize(init, ctx, verbose, force_reinit)
        return self

    def cast(self, dtype):
        for child in self._children.values():
            child.cast(dtype)
        for _, param in self.params.items():
            param.cast(dtype)
        return self

    # -- save/load -------------------------------------------------------
    def save_parameters(self, filename, deduplicate=False):
        """Parity: ``Block.save_parameters`` (params only, by name)."""
        params = self._collect_params_with_prefix()
        from ..ndarray.utils import save as nd_save

        nd_save(filename, {k: v.data() for k, v in params.items()})

    def load_parameters(
        self, filename, ctx=None, allow_missing=False, ignore_extra=False, cast_dtype=False, dtype_source="current"
    ):
        from ..ndarray.utils import load as nd_load

        loaded = nd_load(filename)
        params = self._collect_params_with_prefix()
        if not allow_missing:
            for name in params:
                if name not in loaded:
                    raise IOError(f"Parameter {name} missing in {filename}")
        for name, v in loaded.items():
            if name not in params:
                if not ignore_extra:
                    raise IOError(f"Parameter {name} in {filename} not found in Block")
                continue
            params[name].set_data(v)

    def _collect_params_with_prefix(self, prefix=""):
        if prefix:
            prefix += "."
        ret = {}
        for name, param in self.params.items():
            suffix = name[len(self._params.prefix):] if name.startswith(self._params.prefix) else name
            ret[prefix + suffix] = param
        for cname, child in self._children.items():
            attr = None
            for k, v in self.__dict__.items():
                if v is child:
                    attr = k
                    break
            ret.update(child._collect_params_with_prefix(prefix + (attr or cname)))
        return ret

    save_params = save_parameters

    def load_params(self, filename, ctx=None, **kwargs):
        self.load_parameters(filename, ctx, **kwargs)

    # -- execution -------------------------------------------------------
    def __call__(self, *args, **kwargs):
        if _is_tracing():
            # inside a whole-graph capture every op carries its block's
            # name in the HLO name stack (``spmd.forward/<model>/<layer>/…``
            # in xprof); metadata only, and eager calls enter nothing
            with jax.named_scope(self.name):
                return self._call(*args, **kwargs)
        return self._call(*args, **kwargs)

    def _call(self, *args, **kwargs):
        for hook in self._forward_pre_hooks:
            hook(self, args)
        out = self.forward(*args, **kwargs)
        for hook in self._forward_hooks:
            hook(self, args, out)
        return out

    def forward(self, *args, **kwargs):
        raise NotImplementedError

    def hybridize(self, active=True, **kwargs):
        """No-op on plain Blocks except to recurse (parity)."""
        for child in self._children.values():
            child.hybridize(active, **kwargs)

    def export_jittable(self, training=False, rng_key=None):
        """Return ``(fn, param_arrays)`` — a PURE function over jax arrays.

        ``fn(param_arrays, *input_arrays) -> array | tuple of arrays`` runs
        this block's forward with parameters taken from the ``param_arrays``
        list (sorted by parameter name, matching ``param_arrays``'s order)
        instead of the block's own buffers.  It is safe to ``jax.jit``,
        ``jax.grad``, shard, or export to StableHLO — this is the supported
        surface for driver harnesses and serving (the role
        [U:src/c_api/c_predict_api.cc] plays for the reference), replacing
        any reach into ``_traced_data``/TLS internals.

        ``training`` selects train-mode semantics (dropout live, BatchNorm
        batch stats; aux-state side effects are NOT returned — use
        ``parallel.SPMDTrainer`` for a full training step).  ``rng_key``
        seeds dropout when training (default: a fixed key, so the exported
        fn is deterministic).
        """
        import jax

        params = sorted(self.collect_params().values(), key=lambda p: p.name)
        for p in params:
            if p._data is None:
                raise ValueError(
                    f"Parameter {p.name} is not materialized (deferred init?). "
                    "Run one forward pass before export_jittable().")
        param_arrays = [p._data._data for p in params]
        key = rng_key if rng_key is not None else jax.random.PRNGKey(0)
        block = self

        def fn(param_arrs, *inputs):
            with trace_scope(params, param_arrs, key, training):
                out = block(*[NDArray(x) if x is not None else None
                              for x in inputs])
            if isinstance(out, (list, tuple)):
                return tuple(o._data for o in out)
            return out._data

        return fn, param_arrays

    def summary(self, *inputs):
        """Print a per-layer summary (parity: ``Block.summary``)."""
        rows = []

        def add_hook(block, name):
            def hook(b, inp, out):
                o = out[0] if isinstance(out, (list, tuple)) else out
                n_params = sum(
                    int(_np.prod(p.shape)) for p in b.params.values() if p.shape and all(s > 0 for s in p.shape)
                )
                rows.append((name or b.name, type(b).__name__, tuple(getattr(o, "shape", ())), n_params))

            return hook

        handles = []
        for name, child in self._children.items():
            child._forward_hooks.append(add_hook(child, name))
            handles.append(child)
        try:
            self(*inputs)
        finally:
            for child in handles:
                child._forward_hooks.pop()
        header = f"{'Layer':<28}{'Type':<20}{'Output shape':<24}{'Params':<12}"
        print(header)
        print("-" * len(header))
        for r in rows:
            print(f"{r[0]:<28}{r[1]:<20}{str(r[2]):<24}{r[3]:<12}")

    def __repr__(self):
        lines = [f"{self.__class__.__name__}("]
        for name, child in self._children.items():
            child_repr = repr(child).replace("\n", "\n  ")
            lines.append(f"  ({name}): {child_repr}")
        lines.append(")")
        return "\n".join(lines)


class HybridBlock(Block):
    """A Block whose forward can be compiled (parity: ``gluon.HybridBlock``).

    Subclasses implement ``hybrid_forward(self, F, x, *args, **params)``
    where ``F`` is the nd namespace and params arrive as keyword NDArrays —
    the reference's exact authoring convention, so model code ports 1:1.
    """

    def __init__(self, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._active = False
        self._cached_graph = {}
        self._flags = {}
        from ..base import register_jit_cache_owner
        register_jit_cache_owner(self)

    def _invalidate_jit_cache(self):
        self._cached_graph.clear()

    def hybridize(self, active=True, static_alloc=False, static_shape=False, **kwargs):
        self._active = active
        self._flags = dict(static_alloc=static_alloc, static_shape=static_shape, **kwargs)
        self._cached_graph.clear()
        super().hybridize(active, static_alloc=static_alloc, static_shape=static_shape, **kwargs)
        return self

    def infer_shape(self, *args):
        """Infer deferred parameter shapes by running an abstract forward
        (the reference uses the symbolic shape-inference pass; here
        ``jax.eval_shape`` on the same code)."""
        self._deferred_infer(args)

    def _deferred_infer(self, args):
        # Run once eagerly with recording off; layers finish deferred init
        # inside their hybrid_forward when they see concrete inputs.
        pass

    def cast(self, dtype):
        self._cached_graph.clear()
        return super().cast(dtype)

    # -- parameter plumbing for the compiled path -----------------------
    def _ordered_params(self):
        params = list(self.collect_params().values())
        params.sort(key=lambda p: p.name)
        return params

    def _call_defer_init(self, *args):
        """First call with concrete inputs: finish deferred param init by
        running the eager path under no-grad on a zero-cost abstract trace
        is impossible (init needs shapes only), so layers infer shapes from
        the concrete inputs inside hybrid_forward."""
        return None

    def __call__(self, *args, **kwargs):
        if self._active and not _is_tracing() and not kwargs:
            try:
                return self._call_cached(args)
            except DeferredInit:
                # materialization pass: run eagerly once with aux side
                # effects swallowed (a throwaway collector), then retry the
                # cached path so the first user-visible call compiles +
                # caches AND applies aux updates exactly once
                _aux_stack().append([])
                try:
                    super().__call__(*args, **kwargs)
                finally:
                    _aux_stack().pop()
                try:
                    return self._call_cached(args)
                except DeferredInit:
                    # a param forward never touches can stay deferred;
                    # fall back to plain eager (real side effects)
                    return super().__call__(*args, **kwargs)
        return super().__call__(*args, **kwargs)

    def forward(self, x, *args):
        """Dispatch to hybrid_forward with parameters as kwargs (parity:
        HybridBlock.forward's NDArray branch).  On deferred parameters the
        layer's shape-inference hook runs first (the reference does this via
        the symbolic infer-shape pass)."""
        from ..base import DeferredInitializationError

        def gather():
            out = {}
            for name, param in self.params.items():
                suffix = name[len(self._params.prefix):] if name.startswith(self._params.prefix) else name
                out[suffix] = param.data()
            return out

        try:
            params = gather()
        except DeferredInitializationError:
            self._shape_inference(x, *args)
            params = gather()
        return self.hybrid_forward(nd_mod, x, *args, **params)

    def _shape_inference(self, x, *args):
        """Finish deferred param init from concrete input shapes; layers with
        deferred params override this."""
        raise RuntimeError(
            f"{type(self).__name__} has deferred-init parameters but no "
            "shape-inference hook; initialize with concrete shapes"
        )

    def hybrid_forward(self, F, x, *args, **kwargs):
        raise NotImplementedError

    # -- the CachedOp equivalent ----------------------------------------
    def _call_cached(self, args):
        flat_in = [a for a in args if isinstance(a, NDArray)]
        if len(flat_in) != len(args):
            return super().__call__(*args)
        params = self._ordered_params()
        for p in params:
            if p._deferred_init is not None or p._data is None:
                raise DeferredInit()
        training = autograd.is_training() or autograd.is_recording()
        key_sig = (
            tuple((tuple(a.shape), str(a.dtype)) for a in args),
            training,
        )
        entry = self._cached_graph.get(key_sig)
        fresh = entry is None
        if fresh:
            entry = self._build_cache(args, params, training)
            self._cached_graph[key_sig] = entry
        jit_fn, n_out, aux_params = entry
        key = get_key()
        raw_params = [p._data for p in params]  # NDArray leaves (tape prov)
        all_inputs = list(args) + raw_params
        # inputs produced inside an engine.bulk() scope may hold pending
        # DeferredArrays — jit_fn consumes raw jax arrays directly (this path
        # bypasses ndarray.invoke's resolve loop), so force them here
        for a in all_inputs:
            d = a._data
            if isinstance(d, _Deferred):
                a._data = d._resolve()

        def fn(*arrs, _jit=jit_fn, _key=key):
            return _jit(_key, *arrs)

        tc = _perf() if fresh else None
        node = None
        if autograd.is_recording():
            raws = [a._data for a in all_inputs]
            outs, node = autograd.record_op(fn, raws, all_inputs, {}, name=self.name)
            if node is None:
                outs = fn(*raws)
        else:
            outs = fn(*(a._data for a in all_inputs))
        if tc is not None:
            sig = {"__program__":
                   f"{self.name}:{'train' if training else 'eval'}"}
            for i, (shape, dt) in enumerate(key_sig[0]):
                sig[f"in{i}"] = {"k": "array", "shape": tuple(shape),
                                 "dtype": dt}
            sig["params"] = _profiler.sig_static(len(params))
            _profiler.record_compile("block.cached_op", sig,
                                     (_perf() - tc) * 1e3)
        outs = list(outs)
        aux_new = outs[n_out:]
        outs = outs[:n_out]
        with autograd.pause():
            for p, new in zip(aux_params, aux_new):
                p.set_data(NDArray(new))
        results = []
        for i, o in enumerate(outs):
            r = NDArray(o, ctx=flat_in[0]._ctx if flat_in else current_context())
            if autograd.is_recording() and node is not None:
                r._prov = (node, i)
            results.append(r)
        return results[0] if len(results) == 1 else results

    def _build_cache(self, args, params, training):
        """Trace + compile the whole block tree into one jit callable
        (the CachedOp ctor analog)."""
        n_out_cell = []
        aux_params_cell = []
        block = self

        def pure(key, *arrs):
            n_in = len(args)
            ins = [NDArray(a) for a in arrs[:n_in]]
            with trace_scope(params, arrs[n_in:], key, training) as collector:
                out = block.forward(*ins)
            outs = out if isinstance(out, (list, tuple)) else [out]
            if not n_out_cell:
                n_out_cell.append(len(outs))
                aux_params_cell.extend(p for p, _ in collector)
            return tuple(o._data for o in outs) + tuple(v._data if isinstance(v, NDArray) else v for _, v in collector)

        jit_fn = jax.jit(pure)
        # Populate n_out/aux metadata via an abstract trace (no execution).
        # The probe key is an AVAL, not get_key(): consuming a real split
        # here would shift the ambient PRNG stream by one on every fresh
        # signature — the folded step (step_fold.py) and this path must
        # draw identical per-step keys for dropout parity.
        ex = jax.random.PRNGKey(0)
        jax.eval_shape(pure, jax.ShapeDtypeStruct(ex.shape, ex.dtype),
                       *[jax.ShapeDtypeStruct(a.shape, a.dtype) for a in args],
                       *[jax.ShapeDtypeStruct(p._data.shape, p._data.dtype) for p in params])
        return jit_fn, n_out_cell[0], aux_params_cell

    def export(self, path, epoch=0, remove_amp_cast=True):
        """Export compiled graph + params for deployment (parity:
        ``HybridBlock.export`` — symbol.json + params).  Saves StableHLO
        text instead of nnvm JSON (documented divergence)."""
        params = self._ordered_params()
        if not self._cached_graph:
            raise RuntimeError("Please first call block.hybridize() and then run forward with this block at least once before calling export.")
        from ..ndarray.utils import save as nd_save

        arg_dict = {}
        for p in params:
            prefix = "aux:" if p.grad_req == "null" else "arg:"
            arg_dict[prefix + p.name] = p.data()
        nd_save(f"{path}-{epoch:04d}.params", arg_dict)
        with open(f"{path}-symbol.json", "w") as f:
            import json

            f.write(json.dumps({"format": "stablehlo", "note": "see .mlir"}))
        return f"{path}-symbol.json", f"{path}-{epoch:04d}.params"

    def optimize_for(self, x, *args, backend=None, **kwargs):
        """Parity shim for the subgraph-backend API ([U:src/operator/subgraph/]):
        XLA performs fusion/placement; this simply hybridizes and warms the
        cache."""
        self.hybridize()
        self(x, *args)


class DeferredInit(Exception):
    pass


class SymbolBlock(HybridBlock):
    """Construct a Block from a Symbol graph (parity: ``gluon.SymbolBlock``).
    Implemented once the symbol module lands; see symbol/."""

    def __init__(self, outputs, inputs, params=None):
        super().__init__(prefix=None, params=params)
        self._outputs = outputs
        self._inputs = inputs

    def hybrid_forward(self, F, *args, **params):
        from ..symbol import _eval_symbol

        return _eval_symbol(self._outputs, self._inputs, args, params)
