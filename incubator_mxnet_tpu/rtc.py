"""``mx.rtc`` — runtime-compiled custom kernels, the Pallas way.

Parity target: [U:python/mxnet/rtc.py] (``CudaModule``: compile raw CUDA
C at runtime, ``get_kernel(name, signature)``, ``kernel.launch(args, ctx,
grid_dims, block_dims)``).

TPU-native design: the runtime-kernel story on TPU is **Pallas/Mosaic**,
not NVRTC, so the "source" a :class:`PallasModule` compiles is Pallas
kernel code — either a Python *string* compiled at runtime (the closest
analog of the reference's CUDA-source string) or already-defined kernel
functions.  A kernel body follows the standard Pallas contract: it takes
input ``Ref``s then output ``Ref``s and writes results with ``o[...] =``.
``launch`` mirrors the reference's shape: positional NDArray args, an
optional grid, and it allocates + returns the outputs.

Kernels are compiled by Mosaic for the TPU.  ``PallasModule(...,
interpret=True)`` runs them in the Pallas interpreter instead — asked for
by name (the same discipline as ops/attention.py), which is how the CPU
test tier exercises them; without it a launch off-TPU is a lowering error,
never a quiet switch to the interpreter.  Like the reference, rtc kernels are raw compute: no autograd
(wrap one in ``mx.operator.CustomOp`` to differentiate through it).
"""
from __future__ import annotations

import textwrap

import jax
import jax.numpy as jnp

__all__ = ["PallasModule"]


class Kernel:
    """A launchable kernel (parity shape: ``mx.rtc.CudaKernel``)."""

    def __init__(self, fn, name, out_shapes, out_dtypes, grid, in_specs,
                 out_specs, interpret=False):
        self._fn = fn
        self._interpret = bool(interpret)
        self.name = name
        self._out_shapes = tuple(tuple(s) for s in out_shapes)
        self._out_dtypes = tuple(out_dtypes)
        self._grid = grid
        self._in_specs = in_specs
        self._out_specs = out_specs
        # compiled-once discipline (the reference compiles at get_kernel
        # time): pallas_call closures cached per grid
        self._calls = {}

    def _call(self, grid):
        call = self._calls.get(grid)
        if call is not None:
            return call
        from jax.experimental import pallas as pl

        out_shape = [jax.ShapeDtypeStruct(s, jnp.dtype(d))
                     for s, d in zip(self._out_shapes, self._out_dtypes)]
        single = len(out_shape) == 1
        kwargs = {}
        if grid is not None:
            kwargs["grid"] = grid
        if self._in_specs is not None:
            kwargs["in_specs"] = self._in_specs
        if self._out_specs is not None:
            kwargs["out_specs"] = self._out_specs if not single else self._out_specs[0]
        call = jax.jit(pl.pallas_call(
            self._fn,
            out_shape=out_shape[0] if single else out_shape,
            interpret=self._interpret,
            **kwargs,
        ))
        self._calls[grid] = call
        return call

    def launch(self, args, ctx=None, grid_dims=None):
        """Run the kernel on ``args`` (NDArrays); returns the output
        NDArray, or a tuple when the kernel has several outputs.

        ``grid_dims`` overrides the grid given at ``get_kernel`` time
        (the reference's launch-time grid).  ``ctx`` is accepted for API
        parity; placement follows the inputs, like every other op here.
        """
        from .ndarray.ndarray import NDArray

        del ctx
        grid = grid_dims if grid_dims is not None else self._grid
        if isinstance(grid, list):
            grid = tuple(grid)
        xs = [a._data if isinstance(a, NDArray) else jnp.asarray(a) for a in args]
        out = self._call(grid)(*xs)
        if len(self._out_shapes) == 1:
            return NDArray(out)
        return tuple(NDArray(o) for o in out)


class PallasModule:
    """Compile Pallas kernel source at runtime (parity:
    ``mx.rtc.CudaModule``).

    ``source`` is either a string of Python code defining kernel
    functions (compiled with ``exec`` in a namespace that already has
    ``pl``, ``jnp``, ``jax`` — the runtime-compilation analog of NVRTC),
    or a callable / iterable of callables.  ``exports`` optionally limits
    which names are retrievable, like the reference's exports list.
    ``interpret=True`` runs every kernel of the module in the Pallas
    interpreter (debugging / CPU tests) instead of compiling it for TPU.

    Example::

        src = '''
        def scale_add(x_ref, y_ref, o_ref):
            o_ref[...] = 2.0 * x_ref[...] + y_ref[...]
        '''
        mod = mx.rtc.PallasModule(src, exports=["scale_add"])
        k = mod.get_kernel("scale_add", out_shapes=[(64, 64)])
        z = k.launch([x, y])
    """

    def __init__(self, source, exports=(), interpret=False):
        from jax.experimental import pallas as pl

        self._interpret = bool(interpret)
        self._kernels = {}
        if isinstance(source, str):
            ns = {"pl": pl, "jnp": jnp, "jax": jax}
            exec(compile(textwrap.dedent(source), "<mx.rtc source>", "exec"), ns)
            fns = {k: v for k, v in ns.items()
                   if callable(v) and k not in ("pl", "jnp", "jax")
                   and not k.startswith("__")}
        elif callable(source):
            fns = {source.__name__: source}
        else:
            fns = {f.__name__: f for f in source}
        allowed = set(exports) if exports else None
        for name, fn in fns.items():
            if allowed is None or name in allowed:
                self._kernels[name] = fn
        if allowed is not None and allowed - set(self._kernels):
            missing = sorted(allowed - set(self._kernels))
            raise ValueError(f"exports not found in source: {missing}")

    def get_kernel(self, name, out_shapes, out_dtypes=None, grid=None,
                   in_specs=None, out_specs=None, signature=None):
        """Retrieve a launchable kernel.

        ``out_shapes``/``out_dtypes`` declare the outputs the kernel
        writes (the role the reference's C ``signature`` string played —
        accepted as ``signature`` for drop-in callers and ignored).
        ``grid``/``in_specs``/``out_specs`` are the Pallas launch
        geometry; with no grid the kernel sees whole-array Refs.
        """
        del signature
        if name not in self._kernels:
            raise ValueError(
                f"kernel {name!r} not in module (have {sorted(self._kernels)})")
        if isinstance(out_shapes[0], int):
            out_shapes = [out_shapes]
        if out_dtypes is None:
            out_dtypes = ["float32"] * len(out_shapes)
        elif isinstance(out_dtypes, str):
            out_dtypes = [out_dtypes] * len(out_shapes)
        if len(out_dtypes) != len(out_shapes):
            raise ValueError(
                f"out_dtypes has {len(out_dtypes)} entries for "
                f"{len(out_shapes)} out_shapes")
        if out_specs is not None and not isinstance(out_specs, (list, tuple)):
            out_specs = [out_specs]
        if out_specs is not None and len(out_specs) != len(out_shapes):
            raise ValueError(
                f"out_specs has {len(out_specs)} entries for "
                f"{len(out_shapes)} out_shapes")
        return Kernel(self._kernels[name], name, out_shapes, out_dtypes,
                      grid, in_specs, out_specs, interpret=self._interpret)
