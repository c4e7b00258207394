"""``mx.predictor`` — standalone inference API.

Parity: [U:src/c_api/c_predict_api.cc] (``MXPredCreate`` / SetInput /
Forward / GetOutput) — the embedding-oriented predict surface that loads a
``-symbol.json`` + ``.params`` checkpoint and runs forward-only.  Here the
bound program is one ``jax.jit``-compiled XLA executable (donated inputs,
no autograd machinery), the deployment analog of ``Block.export``.

Since ISSUE 8 the predictor is the binding substrate of the serving tier
(``incubator_mxnet_tpu/serving``): parameters are placed on device ONCE
and **shared by object across every shape bind** — ``reshape(new_shapes)``
swaps the active input-shape signature, reusing both the parameter arrays
and any executor (+ its jit cache) previously bound for that signature.
A shape-bucketed server therefore holds one copy of the weights no matter
how many (batch, length) buckets it serves, and switching buckets costs a
dict lookup, not a device copy or a recompile.

A ``Predictor`` is NOT thread-safe (``reshape``/``set_input``/``forward``
mutate the active executor): concurrent callers must serialize, which is
exactly what ``serving.InferenceServer``'s single scheduler thread does.
"""
from __future__ import annotations

import time as _time

import numpy as _np

__all__ = ["Predictor", "StatefulExecutor", "load_checkpoint"]


def _nd_store_nbytes(nd):
    """Footprint of one stored NDArray — the shared shape-x-dtype rule
    (``profiler.array_nbytes``; never touches the raw buffer)."""
    from . import profiler

    return profiler.array_nbytes(nd)


def _release_predictor_memory(cell):
    """weakref.finalize hook for a predictor's ledger share (mutable cell:
    late-bound zero-filled parameters grow it after construction)."""
    from . import profiler

    profiler.track_memory("predictor.params", "params").free(cell[0])
    cell[0] = 0


def _split_param_key(name):
    """Split a checkpoint key into (kind, bare_name).

    Only the literal ``arg:`` / ``aux:`` prefixes of the reference
    checkpoint format are stripped; any other colon is part of the
    parameter's own name (the old ``split(":", 1)`` mangled e.g. a scoped
    ``encoder:weight`` into ``weight``).  ``kind`` is ``"arg"``, ``"aux"``
    or ``None`` (unprefixed — classified against the symbol's
    argument/aux lists by the caller), so prefixed and unprefixed
    checkpoints load identically."""
    if name.startswith("arg:"):
        return "arg", name[4:]
    if name.startswith("aux:"):
        return "aux", name[4:]
    return None, name


def load_checkpoint(symbol_file, param_file):
    """Load a (symbol, params) checkpoint into ``(symbol, arg_params,
    aux_params)`` NDArray dicts with the ``arg:``/``aux:`` prefixes
    resolved (unprefixed keys are classified against the symbol's
    argument/aux lists — prefixed and bare checkpoints load identically).
    The :class:`Predictor` constructor and the serving tier's AMP path
    (``amp.convert_model`` wants the split dicts) share this loader."""
    from . import symbol as sym_mod
    from .ndarray import utils as nd_utils
    from .ndarray.ndarray import NDArray, array

    sym = sym_mod.load(symbol_file) if isinstance(symbol_file, str) \
        else symbol_file
    loaded = nd_utils.load(param_file) if isinstance(param_file, str) \
        else param_file
    arg_names = set(sym.list_arguments())
    aux_names = set(sym.list_auxiliary_states())
    args, auxs = {}, {}
    for k, v in loaded.items():
        kind, name = _split_param_key(k)
        if kind is None:
            kind = "aux" if name in aux_names and name not in arg_names \
                else "arg"
        nd = v if isinstance(v, NDArray) else array(_np.asarray(v))
        (auxs if kind == "aux" else args)[name] = nd
    return sym, args, auxs


class StatefulExecutor:
    """Bind pure step programs over a shared, donated device state.

    The decode loop of the generation tier (``serving/generation.py``) is
    a *stateful* workload: every iteration consumes the KV cache buffers
    and produces their successors.  A plain ``Predictor`` models the
    opposite contract (stateless forward over immutable parameters), so
    this is the second binding substrate: named jitted programs that all
    read and return one ``{name: array}`` state dict, with the state
    donated on every dispatch — steady-state HBM holds exactly one copy
    of the cache, and mutation is buffer aliasing, not allocation.

    Programs are plain functions ``fn(state, inputs) -> (outputs,
    new_state)`` where ``new_state`` must carry every state key (pass
    unchanged entries straight through — XLA aliases them back onto the
    donated input buffers).  ``run()`` rebinds the state BEFORE reporting
    a detected compile, so a raise-mode compile guard can never leave the
    executor pointing at deleted buffers (the PR 9 ``group_apply``
    discipline).

    Not thread-safe — same contract as :class:`Predictor`: the single
    scheduler thread owns all dispatches.
    """

    def __init__(self, state, name="stateful", compile_site=None):
        self._state = dict(state or {})
        self._name = str(name)
        self._site = compile_site or f"executor.{self._name}"
        self._programs = {}
        self._calls = {}
        self._compiles = 0

    @property
    def state(self):
        """The live state dict (read-only by convention; entries are the
        donated/rebound jax arrays)."""
        return self._state

    def add_program(self, name, fn, donate_state=True):
        """Register ``fn(state, inputs) -> (outputs, new_state)`` under
        ``name``.  ``donate_state`` (default) donates the whole state
        pytree on every call."""
        import jax

        if name in self._programs:
            raise ValueError(f"program {name!r} already bound")
        self._programs[name] = jax.jit(
            fn, donate_argnums=(0,) if donate_state else ())
        self._calls[name] = 0
        return self

    def _signature(self, program, inputs):
        from . import profiler

        sig = {"__program__": program}
        for k, v in self._state.items():
            sig[f"state:{k}"] = profiler.sig_array(v)
        for k, v in (inputs or {}).items():
            if hasattr(v, "shape"):
                sig[k] = profiler.sig_array(v)
            elif isinstance(v, (list, tuple)):
                # a pytree of arrays (e.g. a server's frozen weights):
                # one token per leaf — repr() would pull them to the host
                for i, leaf in enumerate(v):
                    sig[f"{k}[{i}]"] = profiler.sig_array(leaf)
            else:
                sig[k] = profiler.sig_static(v)
        return sig

    def run(self, program, **inputs):
        """Dispatch ``program`` on the current state; rebind the returned
        state; return the outputs.  A call that grew the program's jit
        cache is reported to the compile registry under this executor's
        site (guard raise mode raises AFTER the state is rebound)."""
        from . import profiler

        jfn = self._programs[program]
        before = profiler.jit_cache_size(jfn)
        t0 = _time.perf_counter()
        try:
            outputs, new_state = jfn(self._state, inputs)
        except Exception as e:
            # the stateful dispatch (decode step / KV-cache insert) is an
            # OOM choke point: emit one postmortem naming the top ledger
            # owners before the error surfaces (no-op otherwise)
            profiler.maybe_oom_postmortem(e, f"{self._site}:{program}")
            raise
        wall_ms = (_time.perf_counter() - t0) * 1e3
        missing = set(self._state) - set(new_state)
        if missing:
            raise RuntimeError(
                f"program {program!r} dropped state keys {sorted(missing)} "
                f"— donated buffers are gone; every program must return "
                f"the full state")
        sig = None
        if profiler.jit_cache_size(jfn) > before >= 0:
            sig = self._signature(program, inputs)  # before rebinding
        self._state = dict(new_state)
        self._calls[program] += 1
        if sig is not None:
            self._compiles += 1
            profiler.record_compile(self._site, sig, wall_ms, fn=jfn)
        return outputs

    def is_warm(self, program):
        """True when ``program`` has at least one compiled entry."""
        from . import profiler

        return profiler.jit_cache_size(self._programs[program]) > 0

    def compile_stats(self):
        """{"programs", "entries", "compiles", "calls"} — the generation
        harness diffs this around a traffic run to prove the decode loop
        never compiled after warmup."""
        from . import profiler

        entries = 0
        for fn in self._programs.values():
            n = profiler.jit_cache_size(fn)
            if n > 0:
                entries += n
        return {"programs": len(self._programs), "entries": entries,
                "compiles": self._compiles, "calls": dict(self._calls)}


class Predictor:
    """forward-only executor over (symbol json, params file).

    Parameters
    ----------
    symbol_file : path to ``*-symbol.json`` (or a Symbol instance)
    param_file : path to ``.params``/``.npz`` (or a dict of NDArrays,
        keys optionally ``arg:``/``aux:``-prefixed)
    input_shapes : dict name -> shape of the initially bound signature
    """

    def __init__(self, symbol_file, param_file, input_shapes, dev_type="cpu",
                 dev_id=0):
        import weakref as _weakref

        from . import context as ctx_mod
        from . import profiler

        self._sym, self._arg_store, self._aux_store = load_checkpoint(
            symbol_file, param_file)
        self._ctx = ctx_mod.Context(dev_type, dev_id)
        self._input_shapes = {k: tuple(v) for k, v in input_shapes.items()}
        self._exe_cache = {}   # shape signature -> Executor (jit caches ride)
        self._outputs = None
        # device-memory ledger: the shared parameter store is accounted
        # ONCE here (executors share it by object, so their own bound-
        # array accounting is released in _executor_for); freed on
        # close() or GC, whichever first
        self._mem_cell = [sum(
            _nd_store_nbytes(nd)
            for store in (self._arg_store, self._aux_store)
            for nd in store.values())]
        profiler.track_memory("predictor.params", "params").alloc(
            self._mem_cell[0])
        self._mem_finalizer = _weakref.finalize(
            self, _release_predictor_memory, self._mem_cell)
        self._exe = self._executor_for(self._input_shapes)

    def close(self):
        """Release this predictor's share of the device-memory ledger
        (the arrays themselves are freed by GC as usual).  Idempotent;
        also runs at GC via ``weakref.finalize``."""
        self._mem_finalizer()

    @staticmethod
    def _sig(shapes):
        return tuple(sorted((k, tuple(v)) for k, v in shapes.items()))

    def _executor_for(self, shapes):
        """Executor bound for ``shapes``, from the cache when this
        signature was seen before.  A fresh bind allocates ONLY the input
        placeholders — parameters/aux states are the shared store arrays,
        so the device copy made at construction is the only one ever."""
        import jax.numpy as jnp

        from .base import _as_np_dtype
        from .executor import Executor
        from .ndarray.ndarray import NDArray

        sig = self._sig(shapes)
        exe = self._exe_cache.get(sig)
        if exe is not None:
            return exe
        arg_shapes, _, aux_shapes = self._sym.infer_shape(**shapes)
        arg_dtypes, _, aux_dtypes = self._sym.infer_type(
            **{k: tuple(v) for k, v in shapes.items()})

        args = {}
        for name, shp, dt in zip(self._sym.list_arguments(), arg_shapes,
                                 arg_dtypes):
            if name in shapes:
                dtype = _as_np_dtype(dt or "float32")
                args[name] = NDArray(jnp.zeros(tuple(shapes[name]), dtype))
                continue
            nd = self._arg_store.get(name)
            if nd is None:
                # parameter absent from the checkpoint: bind zeros, but
                # STORE them so later binds share the same array
                if shp is None:
                    raise ValueError(
                        f"predictor: cannot infer shape of unbound "
                        f"parameter {name!r}")
                dtype = _as_np_dtype(dt or "float32")
                nd = self._arg_store[name] = NDArray(jnp.zeros(shp, dtype))
                self._mem_account(nd)
            elif shp is not None and tuple(nd.shape) != tuple(shp):
                raise ValueError(
                    f"predictor: parameter {name!r} has shape "
                    f"{tuple(nd.shape)} but the graph needs {tuple(shp)} "
                    f"for inputs {dict(shapes)} — shape-dependent "
                    f"parameters cannot be shared across binds")
            args[name] = nd
        auxs = {}
        for name, shp, dt in zip(self._sym.list_auxiliary_states(),
                                 aux_shapes, aux_dtypes):
            nd = self._aux_store.get(name)
            if nd is None:
                dtype = _as_np_dtype(dt or "float32")
                nd = self._aux_store[name] = NDArray(
                    jnp.zeros(shp if shp is not None else (1,), dtype))
                self._mem_account(nd)
            auxs[name] = nd
        exe = Executor(self._sym, self._ctx, args=args, grad_req="null",
                       aux_states=auxs)
        # compile-registry attribution: a compile triggered by a predictor
        # bind reports as the predictor's, not a bare executor's (the
        # serving tier further overrides via profiler.compile_site)
        exe._compile_site = "predictor.forward"
        # memory attribution: the executor's bound arrays ARE the shared
        # store this predictor already accounted — drop the executor's own
        # ledger row so the bytes are never counted twice
        exe._release_memory()
        self._exe_cache[sig] = exe
        return exe

    def _mem_account(self, nd):
        n = _nd_store_nbytes(nd)
        if n:
            from . import profiler

            self._mem_cell[0] += n
            profiler.track_memory("predictor.params", "params").alloc(n)

    def reshape(self, new_shapes):
        """Rebind for a new input-shape signature, sharing the parameter
        arrays (no device copy).  A signature seen before reuses its
        executor — and therefore its warm jit cache — outright.  Returns
        ``self`` (the c_predict ``MXPredReshape`` contract: the handle
        stays valid, only the bound shapes change)."""
        new_shapes = {k: tuple(v) for k, v in new_shapes.items()}
        unknown = set(new_shapes) - set(self._input_shapes)
        if unknown:
            raise KeyError(f"unknown input(s) {sorted(unknown)!r}; "
                           f"inputs are {sorted(self._input_shapes)}")
        shapes = dict(self._input_shapes)
        shapes.update(new_shapes)
        self._exe = self._executor_for(shapes)
        self._input_shapes = shapes
        self._outputs = None
        return self

    def is_warm(self, shapes=None):
        """True when the given (default: current) signature has a bound
        executor whose forward program is already compiled — i.e. a
        ``forward`` at this signature will not trigger a jit trace.  The
        serving tier's bucket hit/miss accounting reads this."""
        shapes = dict(self._input_shapes) if shapes is None else \
            {k: tuple(v) for k, v in shapes.items()}
        exe = self._exe_cache.get(self._sig(shapes))
        return exe is not None and len(exe._fwd_cache) > 0

    def compile_stats(self):
        """{"executors": bound signatures, "fwd_entries": compiled forward
        programs across them} — the serving harness diffs this around a
        traffic run to prove zero post-warmup recompiles."""
        return {
            "executors": len(self._exe_cache),
            "fwd_entries": sum(len(e._fwd_cache)
                               for e in self._exe_cache.values()),
        }

    # -- c_predict-style surface ----------------------------------------
    def set_input(self, name, value):
        """``MXPredSetInput``."""
        if name not in self._input_shapes:
            raise KeyError(f"unknown input {name!r}")
        self._exe.arg_dict[name][:] = _np.asarray(
            value.asnumpy() if hasattr(value, "asnumpy") else value)

    def forward(self):
        """``MXPredForward`` — runs the compiled program (is_train=False)."""
        self._outputs = self._exe.forward(is_train=False)
        return self

    def get_output(self, index=0):
        """``MXPredGetOutput`` — numpy copy of output ``index``."""
        if self._outputs is None:
            raise RuntimeError("call forward() first")
        return self._outputs[index].asnumpy()

    def num_outputs(self):
        """``MXPredGetOutputShape``-adjacent: how many outputs the bound
        graph produces (the serving tier sizes its per-output unpadding
        spec from this)."""
        return len(self._sym._outputs)

    def get_outputs(self):
        """Numpy copies of ALL outputs of the last ``forward()`` (the
        multi-output serving path; ``get_output`` stays the single-output
        c_predict surface)."""
        if self._outputs is None:
            raise RuntimeError("call forward() first")
        return [o.asnumpy() for o in self._outputs]

    def predict(self, **inputs):
        """Convenience: set all inputs, forward, return output 0."""
        for k, v in inputs.items():
            self.set_input(k, v)
        return self.forward().get_output(0)
