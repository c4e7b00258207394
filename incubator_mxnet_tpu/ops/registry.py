"""Operator registry — TPU-native replacement for the NNVM op registry.

Parity target: ``NNVM_REGISTER_OP`` + ``FCompute`` dispatch
([U:src/operator/], [U:include/mxnet/op_attr_types.h]).  Differences by
design:

* An op is a **pure function** ``fn(*jax_arrays, **static_kwargs)`` returning
  a jax.Array or tuple thereof.  No FInferShape/FInferType tables are needed —
  ``jax.eval_shape`` performs shape/dtype inference on the same function that
  computes (used by Symbol.infer_shape and deferred Parameter init).
* No FGradient registration — gradients come from ``jax.vjp`` of the same
  pure function (the autograd tape calls it), so every op is differentiable
  for free unless marked ``differentiable=False``.
* CPU/GPU/TPU kernel variants collapse into one definition; XLA specializes
  per backend.

Dispatch cache
--------------
This module also owns level 1 of the eager dispatch accelerator (see
docs/eager_dispatch.md): every ``ndarray.invoke`` of a *registered* op is
routed through a jit-compiled entry cached by

    ``(fn, static argument/kwarg values, input avals+shardings)``

so the steady-state eager hot path replays a compiled XLA executable
instead of re-tracing the op in Python and dispatching one primitive at a
time.  The autograd path caches a jitted vjp alongside (``lookup_recorded``)
so tapes built under ``autograd.record()`` replay compiled code too.

Knobs: ``MXNET_DISPATCH_CACHE=0`` disables the cache,
``MXNET_DISPATCH_CACHE_SIZE`` bounds the LRU (default 512 entries),
``MXNET_DISPATCH_CACHE_WARMUP`` is the number of un-jitted sightings of a
key before compiling it (default 1: one-shot shapes never pay a compile).
``engine.set_engine_type('NaiveEngine')`` bypasses the cache entirely.
"""
from __future__ import annotations

import functools
import inspect
import os
import threading
from collections import OrderedDict
from time import perf_counter as _perf

import jax as _jax
import numpy as _np

# hot-path type constants: attribute chains like ``jax.core.Tracer`` cost a
# dict walk per call at ~100k calls/sec dispatch rates, and
# ``isinstance(x, jax.Array)`` is an ABC __instancecheck__ (~10x the cost of
# an exact type test against the one concrete array class)
_JArray = _jax.Array
_JTracer = _jax.core.Tracer
# the concrete eager array class, WITHOUT running a computation —
# type(jnp.zeros(())) would initialize the XLA backend at import time
# and break jax.distributed.initialize() on multi-host workers
from jax._src.array import ArrayImpl as _ArrayImpl

_SDSharding = _jax.sharding.SingleDeviceSharding
_SCALAR_TYPES = frozenset((bool, int, float, complex, str, type(None)))


def _sharding_token(s):
    """Hashable stand-in for a sharding in cache keys.  SingleDeviceSharding
    (the only kind eager CPU/GPU arrays carry) hashes by recomputation every
    time (~1us); its Device hashes like an int and compares equal exactly
    when the shardings do."""
    if type(s) is _SDSharding:
        return s._device
    return s

__all__ = ["Op", "register", "get_op", "list_ops", "alias",
           "dispatch_eager", "MISS", "lookup_eager", "lookup_recorded",
           "dispatch_cache_stats", "clear_dispatch_cache",
           "dispatch_cache_enabled", "set_dispatch_cache"]

_REGISTRY: dict[str, "Op"] = {}


class Op:
    """A registered operator.

    ``alias()`` registers the *same* ``Op`` object under additional names
    (recorded in ``aliases``), so ``elemwise_add``/``broadcast_add``/
    ``__add__`` share one ``fn`` identity and therefore one dispatch-cache
    entry — the cache key starts with ``fn``, never the name.
    """

    __slots__ = ("name", "fn", "differentiable", "wrap_ndarray", "doc", "aliases")

    def __init__(self, name, fn, differentiable=True, wrap_ndarray=True):
        self.name = name
        self.fn = fn
        self.differentiable = differentiable
        self.wrap_ndarray = wrap_ndarray
        self.doc = fn.__doc__
        self.aliases = []

    def __call__(self, *args, **kwargs):
        return self.fn(*args, **kwargs)

    def __repr__(self):
        return f"<Op {self.name}>"


def register(name=None, differentiable=True, wrap_ndarray=True,
             cacheable=True):
    """Decorator registering a pure function as a framework operator.

    ``cacheable=False`` keeps the op off both levels of the eager dispatch
    accelerator (level-1 jit cache and engine.bulk micro-graphs) — required
    for ops whose body runs arbitrary user python with side effects
    (``Custom``: freezing it into a compiled executable would replay stale
    state and skip the side effects)."""

    def deco(fn):
        opname = name or fn.__name__
        if opname in _REGISTRY:
            raise ValueError(f"op {opname!r} already registered")
        op = Op(opname, fn, differentiable, wrap_ndarray)
        _REGISTRY[opname] = op
        if cacheable:
            _CACHEABLE_FNS[fn] = op
        return fn

    return deco


def alias(new_name, existing):
    """Register an alias for an existing op (MXNet has many, e.g.
    ``elemwise_add`` vs ``broadcast_add`` vs ``__add__``).  The alias shares
    the canonical ``Op`` object — NOT a copy — so the dispatch cache compiles
    the underlying ``fn`` once no matter which name invoked it."""
    op = get_op(existing)
    if new_name in _REGISTRY:
        raise ValueError(f"op {new_name!r} already registered")
    _REGISTRY[new_name] = op
    op.aliases.append(new_name)


def get_op(name):
    try:
        return _REGISTRY[name]
    except KeyError:
        raise KeyError(f"operator {name!r} is not registered") from None


def list_ops():
    return sorted(_REGISTRY)


# ---------------------------------------------------------------------------
# Level-1 eager dispatch cache
# ---------------------------------------------------------------------------

# fn -> Op for every registered pure function; only these are eligible for
# the cache (closures handed to invoke() have no stable identity to key on).
_CACHEABLE_FNS: dict = {}

_enabled = os.environ.get("MXNET_DISPATCH_CACHE", "1") != "0"
_max_entries = int(os.environ.get("MXNET_DISPATCH_CACHE_SIZE", "512"))
_warmup = int(os.environ.get("MXNET_DISPATCH_CACHE_WARMUP", "1"))

_lock = threading.RLock()
_entries: OrderedDict = OrderedDict()   # key -> _Entry (compiled)
_pending: OrderedDict = OrderedDict()   # key -> sighting count (pre-warmup)
_unjittable: set = set()                # (fn, static key parts) that failed to trace

_DYN = object()  # sentinel in arg specs: "comes from the dynamic args"


class _Ineligible(Exception):
    """Raised during classification when a call can't be cached."""


def _scalar_token(tv, v):
    """THE scalar cache-key rule, shared by every non-fast-path key builder
    in this module and engine.py: type-tagged (1, 1.0, True, and
    np.float64(1.0) — a float subclass — are ==/hash-equal but bake
    different dtypes/promotion behavior into a compiled entry) and
    -0.0-split (-0.0 == 0.0 and they hash alike, but baking the wrong zero
    flips signs, e.g. x / -0.0; str() separates them).  The two genuinely
    hot inlined copies (the exact-type branches in _classify_args and
    engine._BulkQueue.enqueue) must mirror any change made here."""
    if isinstance(v, _np.generic):
        item = v.item()
        if isinstance(item, (float, complex)) and item == 0:
            return ("npg", v.dtype.str, item, str(item))
        return ("npg", v.dtype.str, item)
    if isinstance(v, (float, complex)) and v == 0:
        return (tv, v, str(v))
    return (tv, v)


def _static_token(v):
    """Hashable cache token for a static value.  Whitelist-based: anything
    not provably safe to bake into a jitted closure and compare by value
    (arbitrary objects may define exotic __eq__/__hash__, e.g. NDArray)
    raises TypeError → the call stays on the raw path."""
    if v is None:
        return v
    if isinstance(v, (bool, int, float, complex, str, bytes, type,
                      _np.generic)):
        return _scalar_token(type(v), v)
    if isinstance(v, (list, tuple)):
        return ("seq", type(v).__name__, tuple(_static_token(x) for x in v))
    if isinstance(v, dict):
        return ("map", tuple(sorted((k, _static_token(x)) for k, x in v.items())))
    if isinstance(v, _np.dtype):
        return ("dt", v.str)
    raise TypeError(f"not cache-keyable: {type(v)}")


def _aval_token(a):
    # NB: dtype object, not str(dtype) — str() costs ~6us per call on the
    # hottest path in the codebase; np.dtype hashes/compares cheaply
    return (a.shape, a.dtype, a.aval.weak_type, _sharding_token(a.sharding))


def _seq_has_array(v):
    return any(isinstance(x, (_JArray, _np.ndarray))
               or (isinstance(x, (list, tuple)) and _seq_has_array(x))
               for x in v)


_SCALARS = (bool, int, float, complex, str)


def _classify_args(raw_args):
    """Split positional args into (spec, key_parts, dyn_args).

    spec is a tuple with ``_DYN`` markers where a dynamic value is
    substituted at call time and literal values for statics (baked into the
    jitted closure; their tokens are part of the key).
    """
    spec, key, dyn = [], [], []
    for a in raw_args:
        ta = type(a)
        if ta is _ArrayImpl:  # exact test dodges the jax.Array ABC check
            key.append(("a", a.shape, a.dtype, a.aval.weak_type,
                        _sharding_token(a.sharding)))
            spec.append(_DYN)
            dyn.append(a)
            continue
        if ta in _SCALAR_TYPES:
            # scalars are STATIC (baked trace constants, keyed by type+value):
            # a dynamic scalar arg defeats jit's C++ fast dispatch path and
            # costs ~2x per call; eager chains overwhelmingly reuse the same
            # literal, and one-shot values never compile thanks to warmup
            if (ta is float or ta is complex) and a == 0:
                # -0.0 == 0.0 and they hash alike, but baking the wrong
                # zero flips signs (x / -0.0); str() splits them
                key.append(("s", ta, a, str(a)))
            else:
                key.append(("s", ta, a))
            spec.append(a)
            continue
        if isinstance(a, _JTracer):
            raise _Ineligible  # inside hybridize/SPMD traces: raw fallthrough
        if isinstance(a, _JArray):
            key.append(("a", a.shape, a.dtype, a.aval.weak_type,
                        _sharding_token(a.sharding)))
            spec.append(_DYN)
            dyn.append(a)
        elif isinstance(a, _SCALARS):
            # scalar subclasses (np.float64 subclasses float!): shared rule
            key.append(("s", _scalar_token(ta, a)))
            spec.append(a)
        elif isinstance(a, _np.ndarray):
            key.append(("n", a.shape, a.dtype.str))
            spec.append(_DYN)
            dyn.append(a)
        elif isinstance(a, _np.generic):
            key.append(("s", _scalar_token(ta, a)))
            spec.append(a)
        elif isinstance(a, (list, tuple)) and _seq_has_array(a):
            # pytree argument (e.g. add_n's array list): dynamic as a whole
            sub_spec, sub_key, _ = _classify_args(list(a))
            if any(s is not _DYN for s in sub_spec):
                raise _Ineligible  # mixed static/dynamic nesting: keep it raw
            key.append(("t", type(a).__name__, tuple(sub_key)))
            spec.append(_DYN)
            dyn.append(a)
        else:
            try:
                key.append(("s", _static_token(a)))
            except TypeError:
                raise _Ineligible from None
            spec.append(a)
    return tuple(spec), tuple(key), dyn


def _classify_kwargs(kwargs, jax=None):
    """Split kwargs into static (baked, keyed by value) and dynamic
    (jax.Array-valued, keyed by aval) parts."""
    static, key, dyn_names, dyn_vals = {}, [], [], []
    for k in sorted(kwargs):
        v = kwargs[k]
        if isinstance(v, _JTracer):
            raise _Ineligible
        if isinstance(v, _JArray):
            key.append(("ka", k) + _aval_token(v))
            dyn_names.append(k)
            dyn_vals.append(v)
        else:
            try:
                key.append(("ks", k, _static_token(v)))
            except TypeError:
                raise _Ineligible from None
            static[k] = v
    return static, tuple(key), tuple(dyn_names), dyn_vals


# flat memo of _reads_ambient_prng used by dispatch_eager: one dict get on
# the hot path instead of the lru_cache C wrapper + a kwargs.get per call
_PRNG_FNS: dict = {}


@functools.lru_cache(maxsize=None)
def _reads_ambient_prng(fn):
    """Ops with a ``key=None`` parameter split the process PRNG key at call
    time (Dropout, samplers) — caching them without an explicit key would
    freeze the randomness into the executable."""
    try:
        return "key" in inspect.signature(fn).parameters
    except (TypeError, ValueError):
        return True  # can't introspect: stay off the fast path


def _cache_key(fn, raw_args, kwargs):
    """Returns (key, spec, dyn_args, static_kwargs, dyn_kw_names, dyn_kw_vals)
    or raises _Ineligible."""
    if fn not in _CACHEABLE_FNS:
        raise _Ineligible
    if _reads_ambient_prng(fn) and kwargs.get("key") is None:
        raise _Ineligible
    spec, akey, dyn = _classify_args(raw_args)
    if not kwargs:
        return (fn, akey, ()), spec, dyn, {}, (), []
    static_kw, kkey, dyn_kw_names, dyn_kw_vals = _classify_kwargs(kwargs)
    return (fn, akey, kkey), spec, dyn, static_kw, dyn_kw_names, dyn_kw_vals


class _Entry:
    __slots__ = ("fwd", "bwd", "call", "spec")

    # NB: fwd stays a pjit wrapper, NOT an AOT ``.lower().compile()``d
    # object — Compiled.__call__ is a pure-Python path (~1.5x slower per
    # call than pjit's C++ fast dispatch on repeat avals)

    def __init__(self, call, spec, jax):
        self.call = call            # un-jitted (dyn_args, dyn_kw) -> out
        self.spec = spec            # per-positional-arg _DYN/static markers
        self.fwd = jax.jit(call)
        self.bwd = {}               # needs mask -> jitted (dyn, kw, cots) -> grads


def _make_caller(fn, spec, static_kwargs, dyn_kw_names):
    def call(dyn_args, dyn_kw_vals):
        it = iter(dyn_args)
        args = [next(it) if s is _DYN else s for s in spec]
        if static_kwargs or dyn_kw_names:
            kw = dict(static_kwargs)
            kw.update(zip(dyn_kw_names, dyn_kw_vals))
            return fn(*args, **kw)
        return fn(*args)
    return call


_prof = None


def _counters():
    global _prof, _incr
    if _prof is None:
        from .. import profiler as _p

        _prof = _p
        _incr = _p.incr
    return _prof


def _incr(name):  # rebound to profiler.incr on first use (import-cycle dodge)
    _counters().incr(name)


def _compile_tok(tok):
    """Cache-key token -> compile-registry signature token (the key
    already carries exactly what the compiled entry specializes on)."""
    kind = tok[0]
    if kind == "a" or kind == "ka":
        off = 1 if kind == "a" else 2
        t = {"k": "array", "shape": tuple(tok[off]),
             "dtype": str(tok[off + 1])}
        spec = getattr(tok[off + 3], "spec", None)
        if spec is not None:
            t["sharding"] = str(spec)
        return t
    if kind == "n":
        return {"k": "array", "shape": tuple(tok[1]), "dtype": str(tok[2])}
    off = 2 if kind == "ks" else 1
    return {"k": "static", "value": repr(tok[off] if len(tok) == off + 1
                                         else tok[off:])[:120]}


def _compile_sig(fn, akey, kkey):
    """Compile-registry signature for a level-1 cache entry: per-position
    array/static tokens namespaced by the op (__program__), so a new op's
    first compile is never misattributed as another op's recompile.  A
    pytree argument ("t" token, e.g. add_n's array list) expands into one
    entry per leaf — ``arg0[2]`` — so a drift inside the list attributes
    at the leaf with its real kind (shape/dtype), not as an opaque
    static-value change."""
    sig = {"__program__": getattr(fn, "__name__", str(fn))}
    for i, tok in enumerate(akey):
        if tok[0] == "t":
            for j, sub in enumerate(tok[2]):
                sig[f"arg{i}[{j}]"] = _compile_tok(sub)
        else:
            sig[f"arg{i}"] = _compile_tok(tok)
    for tok in kkey:
        sig[str(tok[1])] = _compile_tok(tok)
    return sig


def _get_entry(fn, raw_args, kwargs):
    """Core lookup: returns (entry, dyn_args, dyn_kw_vals, key, fresh)
    when a compiled entry exists (counting a hit; ``fresh`` means this
    call just created it, so its first execution pays trace+compile), or
    None (counting a miss/bypass) when the call should take the raw path
    this time."""
    try:
        key, spec, dyn, static_kw, dkn, dkv = _cache_key(fn, raw_args, kwargs)
    except _Ineligible:
        _incr("dispatch_cache_bypass")
        return None
    # hit path is lock-free: C OrderedDict ops are GIL-atomic, and a lost
    # move_to_end race only perturbs LRU order, never correctness
    entry = _entries.get(key)
    if entry is not None:
        try:
            _entries.move_to_end(key)
        except KeyError:
            pass  # concurrently evicted; the fetched entry is still valid
        _incr("dispatch_cache_hit")
        return entry, dyn, dkv, key, False
    entry = _miss(fn, key, spec, static_kw, dkn)
    if entry is None:
        return None
    return entry, dyn, dkv, key, True


def _blacklist(fn, key):
    """Drop a failed entry and remember not to recompile it (under _lock).
    Keyed per exact (fn, statics, avals) key, so a shape-independent trace
    failure is re-attempted once per new input shape; bounded so
    variable-shape workloads can't grow the set without limit (a clear just
    costs the occasional repeat failed compile)."""
    _entries.pop(key, None)
    _unjittable.add((fn, key[1], key[2]))
    if len(_unjittable) > 4 * _max_entries:
        _unjittable.clear()


MISS = object()  # dispatch_eager sentinel: caller must run the raw fn


def dispatch_eager(fn, raw_args, kwargs):
    """Level-1 fast path for non-recorded eager dispatch.

    Returns the op's raw output when served from a compiled cache entry,
    else the ``MISS`` sentinel (caller runs the raw fn).  Never raises for
    cache reasons: a key that fails to trace is blacklisted and the genuine
    error is re-raised from the raw eager call so user-visible errors keep
    eager semantics.
    """
    if not _enabled:
        return MISS
    # inlined _cache_key + hit lookup: this runs once per eager op call
    try:
        prng = _PRNG_FNS.get(fn)
        if prng is None:
            if fn not in _CACHEABLE_FNS:
                raise _Ineligible
            prng = _PRNG_FNS[fn] = _reads_ambient_prng(fn)
        if prng and kwargs.get("key") is None:
            raise _Ineligible
        spec, akey, dyn = _classify_args(raw_args)
        if kwargs:
            static_kw, kkey, dkn, dkv = _classify_kwargs(kwargs)
        else:
            static_kw, kkey, dkn, dkv = {}, (), (), []
    except _Ineligible:
        _incr("dispatch_cache_bypass")
        return MISS
    key = (fn, akey, kkey)
    # hit path is lock-free: C OrderedDict ops are GIL-atomic, and a lost
    # move_to_end race only perturbs LRU order, never correctness
    entry = _entries.get(key)
    fresh = False
    if entry is None:
        entry = _miss(fn, key, spec, static_kw, dkn)
        if entry is None:
            return MISS
        fresh = True  # first fwd call traces+compiles: the jit-trace span
    else:
        try:
            _entries.move_to_end(key)
        except KeyError:
            pass  # concurrently evicted; the fetched entry is still valid
        _incr("dispatch_cache_hit")
    prof = _prof
    t0 = _perf() if (prof is not None and prof._active) else None
    tc = _perf() if fresh else None
    try:
        out = entry.fwd(tuple(dyn), tuple(dkv))
    except Exception:
        # Re-run raw: if *that* succeeds the failure was a jit artifact
        # (concretization on a dynamic value, etc.) — blacklist the key
        # family.  If raw raises too, the error was genuine and propagates
        # with eager semantics.
        out = fn(*raw_args, **kwargs)
        with _lock:
            _blacklist(fn, key)
        _counters().incr("dispatch_cache_fallback")
        if t0 is not None:
            prof.record_span("dispatch.fallback", "dispatch", t0)
        return out
    if t0 is not None:
        prof.record_span("dispatch.jit_compile" if fresh
                         else "dispatch.cache_hit", "dispatch", t0)
    if fresh:
        # compile registry AFTER the fallback try-block: a guard in raise
        # mode must surface, not blacklist the entry as a jit failure
        _counters().record_compile("ops.dispatch", _compile_sig(fn, akey, kkey),
                                   (_perf() - tc) * 1e3)
    return out


def _miss(fn, key, spec, static_kw, dkn):
    """Slow half of dispatch_eager: warmup accounting and entry compilation
    under the registry lock.  Returns the new entry or None (raw path)."""
    with _lock:
        entry = _entries.get(key)
        if entry is not None:
            _incr("dispatch_cache_hit")
            return entry
        if (fn, key[1], key[2]) in _unjittable:
            _incr("dispatch_cache_bypass")
            return None
        _incr("dispatch_cache_miss")
        seen = _pending.get(key, 0) + 1
        if seen <= _warmup:
            # not hot yet: remember the sighting, stay on the raw path
            _pending[key] = seen
            _pending.move_to_end(key)
            while len(_pending) > 4 * _max_entries:
                _pending.popitem(last=False)
            return None
        _pending.pop(key, None)
        entry = _Entry(_make_caller(fn, spec, static_kw, dkn), spec, _jax)
        _entries[key] = entry
        while len(_entries) > _max_entries:
            _entries.popitem(last=False)
    return entry


def lookup_eager(fn, raw_args, kwargs):
    """Compatibility shim over :func:`dispatch_eager` returning the old
    ``(hit, out)`` pair (tests and external callers)."""
    out = dispatch_eager(fn, raw_args, kwargs)
    if out is MISS:
        return False, None
    return True, out


def _make_bwd(call, diff_pos, jax):
    def bwd(dyn_args, dyn_kw_vals, cots):
        def pure(*diff):
            full = list(dyn_args)
            for p, d in zip(diff_pos, diff):
                full[p] = d
            out = call(tuple(full), dyn_kw_vals)
            return out if isinstance(out, tuple) else (out,)

        _, vjp = jax.vjp(pure, *[dyn_args[p] for p in diff_pos])
        return vjp(cots)
    return bwd


def lookup_recorded(fn, raw_args, kwargs, needs):
    """Level-1 fast path for dispatch under ``autograd.record()``.

    Returns ``(outs_tuple, vjp_fn, pure, diff_in)`` where ``vjp_fn`` replays
    a cached jitted vjp (rematerializing the forward inside the compiled
    backward, so no residuals persist beyond the input arrays), or ``None``
    when the caller should take the raw ``jax.vjp`` path.  ``pure`` and
    ``diff_in`` satisfy the tape's grad-of-grad replay contract
    (autograd._grad_create_graph re-derives the vjp from them eagerly).
    """
    if not _enabled:
        return None
    jax = _jax
    found = _get_entry(fn, raw_args, kwargs)
    if found is None:
        return None
    entry, dyn, dkv, key, fresh = found
    dyn = tuple(dyn)
    dkv = tuple(dkv)
    # positions of the grad-needing inputs within the dynamic-arg tuple:
    # every needing input is an unwrapped NDArray, hence dynamic
    diff_pos, dyn_i = [], 0
    for a_needs, s in zip(needs, entry.spec):
        if s is _DYN:
            if a_needs:
                diff_pos.append(dyn_i)
            dyn_i += 1
        elif a_needs:  # needing input landed in a static slot: not cacheable
            return None
    diff_pos = tuple(diff_pos)

    prof = _prof
    t0 = _perf() if (prof is not None and prof._active) else None
    tc = _perf() if fresh else None
    try:
        out = entry.fwd(dyn, dkv)
    except Exception:
        # blacklist and hand control back to record_op's raw jax.vjp path:
        # a genuine user error re-raises from there with eager semantics
        # (no need to probe-run fn here — that would execute the op twice)
        with _lock:
            _blacklist(fn, key)
        _counters().incr("dispatch_cache_fallback")
        return None
    if t0 is not None:
        prof.record_span("dispatch.jit_compile" if fresh
                         else "dispatch.cache_hit", "dispatch", t0)
    if fresh:
        _counters().record_compile("ops.dispatch",
                                   _compile_sig(fn, key[1], key[2]),
                                   (_perf() - tc) * 1e3)
    outs = out if isinstance(out, tuple) else (out,)

    bwd = entry.bwd.get(diff_pos)
    if bwd is None:
        bwd = jax.jit(_make_bwd(entry.call, diff_pos, jax))
        entry.bwd[diff_pos] = bwd

    def vjp_fn(cots, _bwd=bwd, _call=entry.call, _pos=diff_pos,
               _dyn=dyn, _dkv=dkv):
        cots = tuple(cots)
        p = _prof
        tb = _perf() if (p is not None and p._active) else None
        try:
            grads = _bwd(_dyn, _dkv, cots)
            if tb is not None:
                p.record_span("dispatch.backward", "dispatch", tb)
            return grads
        except Exception:
            # mirror the forward fallback: eager vjp keeps correctness if
            # the jitted backward trips on something the forward didn't
            # (built lazily — this path is exceptional)
            return _make_bwd(_call, _pos, _jax)(_dyn, _dkv, cots)

    # grad-of-grad replay contract: a pure fn over just the diff inputs
    # plus their record-time snapshots
    def pure(*diff, _call=entry.call, _dyn=dyn, _dkv=dkv, _pos=diff_pos):
        full = list(_dyn)
        for p, d in zip(_pos, diff):
            full[p] = d
        out = _call(tuple(full), _dkv)
        return out if isinstance(out, tuple) else (out,)

    diff_in = [dyn[p] for p in diff_pos]
    return outs, vjp_fn, pure, diff_in


def dispatch_cache_stats():
    """Snapshot of cache occupancy (counters live in mx.profiler)."""
    with _lock:
        return {
            "entries": len(_entries),
            "pending": len(_pending),
            "blacklisted": len(_unjittable),
            "enabled": _enabled,
            "max_entries": _max_entries,
            "warmup": _warmup,
        }


def clear_dispatch_cache():
    """Drop all compiled entries, warmup counts, and blacklists (used by
    amp.init-style global-semantics flips and tests)."""
    with _lock:
        _entries.clear()
        _pending.clear()
        _unjittable.clear()
    _reads_ambient_prng.cache_clear()
    _PRNG_FNS.clear()


def dispatch_cache_enabled():
    return _enabled


def set_dispatch_cache(enabled=None, max_entries=None, warmup=None):
    """Runtime control of the level-1 cache; returns previous settings."""
    global _enabled, _max_entries, _warmup
    prev = (_enabled, _max_entries, _warmup)
    with _lock:
        if enabled is not None:
            _enabled = bool(enabled)
        if max_entries is not None:
            _max_entries = int(max_entries)
            while len(_entries) > _max_entries:
                _entries.popitem(last=False)
        if warmup is not None:
            _warmup = int(warmup)
    return prev
