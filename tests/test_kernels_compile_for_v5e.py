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

from incubator_mxnet_tpu.ops import attention as attn_ops
from incubator_mxnet_tpu.ops import moe as moe_ops
from incubator_mxnet_tpu.ops import sparse_attention as sa_ops


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


# cell: tokens, model width, expert width, experts a token, form, the row bucket the cell runs in,
# experts held
EXPERT_LAYERS = {
    "nemotron-3-nano-30b-a3b.clm-s8192": (8192, 2688, 1856, 6, "relu2", 4608, 8),
    "xing4.0-29b-a4b.clm-s4096": (4096, 3584, 1024, 4, "swiglu", 3072, 8),
    "keye-vl-2.0-30b-a3b.clm-s8192": (8192, 2048, 768, 8, "swiglu", 12288, 16),
    "nemotron-3-nano-30b-a3b.clm-s8192, every pair here": (8192, 2688, 1856, 6, "relu2", 49152, 8),
}


@pytest.mark.parametrize("cell", EXPERT_LAYERS)
def test_the_held_experts_products_compile_at_the_cells_widths(cell, one_chip, no_compile_cache):
    """Forward, input gradient and weight gradient of both grouped products
    of a layer (six Mosaic calls), bf16; 1856 is no whole number of 128-lane
    tiles, and the kernels take it as the whole extent of their blocks."""
    tokens, d, h, top_k, form, rows, held = EXPERT_LAYERS[cell]
    spec = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def loss(xt, gate, w_in, w_down, order, group_sizes, n_here):
        return moe_ops._experts_on_rows(rows, xt, order, gate, group_sizes, n_here, w_in, w_down,
                                        top_k, form, "pallas").sum()

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3))).lower(
        spec((tokens, d), jnp.bfloat16), spec((tokens * top_k,), jnp.float32),
        spec((held, d, h * (2 if form == "swiglu" else 1)), jnp.bfloat16),
        spec((held, h, d), jnp.bfloat16), spec((tokens * top_k,), jnp.int32),
        spec((held,), jnp.int32), spec((), jnp.int32)).compile()
    assert compiled.as_text().count('custom_call_target="tpu_custom_call"') == 6


def test_the_selected_attention_kernels_compile_at_the_keye_cells_shapes(one_chip, no_compile_cache):
    """The blockwise forward (with its log-sum-exp) and one-pass backward
    under an int8 selection shared by the heads, and the head-averaged
    probabilities of the indexer's loss: 32 query heads on 4 key/value heads
    of 128 at S 8192, bf16, 512 x 512 blocks (three Mosaic calls and a
    fourth: the int8 blocks, their widening inside the kernels and the
    guarded online-softmax update are what an interpreter does not check)."""
    s, scale = 8192, 128 ** -0.5
    spec = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    q, kv = spec((1, 32, s, 128), jnp.bfloat16), spec((1, 4, s, 128), jnp.bfloat16)
    select = spec((1, s, s), jnp.int8)
    launch = attn_ops._Launch(False, (512, 512))

    def loss(q, k, v, select):
        out, lse = attn_ops._flash_kernels_lse(q, k, v, True, scale, launch, select)
        return out.astype(jnp.float32).sum(), lse

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2), has_aux=True)).lower(
        q, kv, kv, select).compile()
    assert compiled.as_text().count('custom_call_target="tpu_custom_call"') == 2
    probs = jax.jit(lambda q, k, lse, select: sa_ops._head_probs_pallas(
        q, k, lse, select, scale=scale, blocks=(512, 512))).lower(
        q, kv, spec((1, 32, s), jnp.float32), select).compile()
    assert probs.as_text().count('custom_call_target="tpu_custom_call"') == 1


def test_the_index_scores_kernels_compile_at_the_keye_cells_shape(one_chip, no_compile_cache):
    """The indexer's scores of 16 index heads of 64 on one index key at S
    8192, float32, 512 x 512 tiles: the forward kernel and the one backward
    kernel (bfloat16 stacks of two parts a 128-lane column, the float32 tile
    turned inside the kernel, products with a transposed left operand, dk's
    [S, 128] block resident for the whole grid, 100 MiB of VMEM asked for:
    what an interpreter does not check).  The gradient of a scalar that
    needs the scores holds exactly the two, and the dispatcher's predicate
    takes these operands."""
    s = 8192
    spec = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)
    q, k, w = spec(1, s, 16, 64), spec(1, s, 64), spec(1, s, 16)
    launch = attn_ops._Launch(False, (512, 512))
    assert sa_ops._index_path(jax.ShapeDtypeStruct(q.shape, q.dtype), 512, 512) is None   # here: a CPU

    forward = jax.jit(lambda q, k, w: sa_ops._index_scores_kernels(q, k, w, launch)).lower(
        q, k, w).compile()
    assert forward.as_text().count('custom_call_target="tpu_custom_call"') == 1

    def loss(q, k, w, select, target):
        return sa_ops.indexer_kl_loss(sa_ops._index_scores_kernels(q, k, w, launch), select, target)

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        q, k, w, jax.ShapeDtypeStruct((1, s, s), jnp.int8, sharding=one_chip), spec(1, s, s)).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    assert "index_scores_fwd" in text and "index_scores_bwd" in text
