"""mx.rtc — runtime-compiled Pallas kernels (parity idiom:
tests/python/gpu/test_operator_gpu.py::test_cuda_rtc in the reference:
compile source at runtime, launch, check values)."""
import numpy as np
import pytest

import incubator_mxnet_tpu as mx


SRC = '''
def scale_add(x_ref, y_ref, o_ref):
    o_ref[...] = 2.0 * x_ref[...] + y_ref[...]

def saxpy_block(x_ref, y_ref, o_ref):
    # blocked variant: each grid step sees one (8, 128) tile
    o_ref[...] = 0.5 * x_ref[...] + y_ref[...]
'''


def test_string_source_compile_and_launch():
    mod = mx.rtc.PallasModule(SRC, exports=["scale_add", "saxpy_block"], interpret=True)
    x = mx.nd.array(np.random.rand(16, 128).astype(np.float32))
    y = mx.nd.array(np.random.rand(16, 128).astype(np.float32))
    k = mod.get_kernel("scale_add", out_shapes=[(16, 128)])
    z = k.launch([x, y])
    np.testing.assert_allclose(z.asnumpy(), 2 * x.asnumpy() + y.asnumpy(),
                               rtol=1e-6)


def test_grid_launch_with_block_specs():
    from jax.experimental import pallas as pl

    mod = mx.rtc.PallasModule(SRC, interpret=True)
    n_blocks = 4
    spec = pl.BlockSpec((8, 128), lambda i: (i, 0))
    k = mod.get_kernel("saxpy_block", out_shapes=[(8 * n_blocks, 128)],
                       grid=(n_blocks,), in_specs=[spec, spec],
                       out_specs=[spec])
    x = mx.nd.array(np.random.rand(8 * n_blocks, 128).astype(np.float32))
    y = mx.nd.array(np.random.rand(8 * n_blocks, 128).astype(np.float32))
    z = k.launch([x, y])
    np.testing.assert_allclose(z.asnumpy(), 0.5 * x.asnumpy() + y.asnumpy(),
                               rtol=1e-6)


def test_callable_source_and_multiple_outputs():
    def minmax(x_ref, lo_ref, hi_ref):
        lo_ref[...] = x_ref[...].min(keepdims=True)
        hi_ref[...] = x_ref[...].max(keepdims=True)

    mod = mx.rtc.PallasModule(minmax, interpret=True)
    k = mod.get_kernel("minmax", out_shapes=[(1, 1), (1, 1)])
    x = mx.nd.array(np.random.rand(32, 32).astype(np.float32))
    lo, hi = k.launch([x])
    np.testing.assert_allclose(lo.asnumpy().ravel(), [x.asnumpy().min()],
                               rtol=1e-6)
    np.testing.assert_allclose(hi.asnumpy().ravel(), [x.asnumpy().max()],
                               rtol=1e-6)


def test_unknown_kernel_and_missing_export():
    mod = mx.rtc.PallasModule(SRC, interpret=True)
    with pytest.raises(ValueError):
        mod.get_kernel("nope", out_shapes=[(2, 2)])
    with pytest.raises(ValueError):
        mx.rtc.PallasModule(SRC, exports=["not_there"], interpret=True)


def test_indented_source_dedents():
    src = '''
        def twice(x_ref, o_ref):
            o_ref[...] = 2.0 * x_ref[...]
    '''
    mod = mx.rtc.PallasModule(src, interpret=True)
    x = mx.nd.array(np.random.rand(4, 8).astype(np.float32))
    z = mod.get_kernel("twice", out_shapes=[(4, 8)]).launch([x])
    np.testing.assert_allclose(z.asnumpy(), 2 * x.asnumpy(), rtol=1e-6)


def test_bare_out_spec_and_dtype_validation():
    from jax.experimental import pallas as pl

    mod = mx.rtc.PallasModule(SRC, interpret=True)
    spec = pl.BlockSpec((8, 128), lambda i: (i, 0))
    k = mod.get_kernel("saxpy_block", out_shapes=[(16, 128)], grid=(2,),
                       in_specs=[spec, spec], out_specs=spec)  # bare spec
    x = mx.nd.array(np.random.rand(16, 128).astype(np.float32))
    y = mx.nd.array(np.random.rand(16, 128).astype(np.float32))
    np.testing.assert_allclose(k.launch([x, y]).asnumpy(),
                               0.5 * x.asnumpy() + y.asnumpy(), rtol=1e-6)
    with pytest.raises(ValueError):
        mod.get_kernel("scale_add", out_shapes=[(2, 2), (2, 2)],
                       out_dtypes=["float32"])


def test_launch_reuses_compiled_call():
    mod = mx.rtc.PallasModule(SRC, interpret=True)
    k = mod.get_kernel("scale_add", out_shapes=[(8, 8)])
    x = mx.nd.array(np.ones((8, 8), np.float32))
    k.launch([x, x])
    k.launch([x, x])
    assert len(k._calls) == 1  # second launch hit the cache


def test_out_specs_count_validated_at_get_kernel():
    from jax.experimental import pallas as pl

    mod = mx.rtc.PallasModule(SRC, interpret=True)
    spec = pl.BlockSpec((8, 128), lambda i: (i, 0))
    with pytest.raises(ValueError):
        mod.get_kernel("scale_add", out_shapes=[(2, 2), (2, 2)],
                       out_dtypes=["float32", "float32"],
                       grid=(1,), out_specs=spec)
