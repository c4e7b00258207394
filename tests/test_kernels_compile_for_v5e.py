"""The main path's Mosaic kernels at the benchmark cells' REAL widths, compiled
for a TPU v5e that is described and not attached (the TPU's compiler is
installed here): what the chip's compiler would refuse — a block the tiling
does not take, more VMEM than a kernel may use — fails here, at no chip time.
Nothing runs, so nothing is said about results or times.

ONE file, and the topology is described inside a fixture: only the worker
that runs this file loads the TPU's library, and only once a test of it has
started."""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from incubator_mxnet_tpu.ops import moe as moe_ops


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture()
def no_compile_cache():
    """A compile for a described device is written to the persistent cache
    and cannot be read back without the chip: keep it out."""
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


# cell: tokens, model width, expert width, experts a token, form, the row bucket the cell runs in
EXPERT_LAYERS = {
    "nemotron-3-nano-30b-a3b.clm-s8192": (8192, 2688, 1856, 6, "relu2", 4608),
    "xing4.0-29b-a4b.clm-s4096": (4096, 3584, 1024, 4, "swiglu", 3072),
    "nemotron-3-nano-30b-a3b.clm-s8192, every pair here": (8192, 2688, 1856, 6, "relu2", 49152),
}


@pytest.mark.parametrize("cell", EXPERT_LAYERS)
def test_the_held_experts_products_compile_at_the_cells_widths(cell, one_chip, no_compile_cache):
    """Forward, input gradient and weight gradient of both grouped products
    of a layer (six Mosaic calls), bf16; 1856 is no whole number of 128-lane
    tiles, and the kernels take it as the whole extent of their blocks."""
    tokens, d, h, top_k, form, rows = EXPERT_LAYERS[cell]
    spec = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def loss(xt, gate, w_in, w_down, order, group_sizes, n_here):
        return moe_ops._experts_on_rows(rows, xt, order, gate, group_sizes, n_here, w_in, w_down,
                                        top_k, form, "pallas").sum()

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3))).lower(
        spec((tokens, d), jnp.bfloat16), spec((tokens * top_k,), jnp.float32),
        spec((8, d, h * (2 if form == "swiglu" else 1)), jnp.bfloat16),
        spec((8, h, d), jnp.bfloat16), spec((tokens * top_k,), jnp.int32),
        spec((8,), jnp.int32), spec((), jnp.int32)).compile()
    assert compiled.as_text().count('custom_call_target="tpu_custom_call"') == 6
