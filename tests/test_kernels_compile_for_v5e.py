"""The main path's Mosaic kernels at the benchmark cells' REAL widths, compiled
for a TPU v5e that is described and not attached (the TPU's compiler is
installed here): what the chip's compiler would refuse — a block the tiling
does not take, more VMEM than a kernel may use — fails here, at no chip time.
Nothing runs, so nothing is said about results or times.

ONE file, and the topology is described inside a fixture: only the worker
that runs this file loads the TPU's library, and only once a test of it has
started."""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from incubator_mxnet_tpu.ops import attention as attn_ops
from incubator_mxnet_tpu.ops import moe as moe_ops
from incubator_mxnet_tpu.ops import sparse_attention as sa_ops
from incubator_mxnet_tpu.ops import ssm as ssm_ops


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
    "solar-open2-250b.clm-s4096": (4096, 4096, 1280, 8, "swiglu", 1280, 8),
    "lfm2-24b-a2b.clm-b2-s8192": (16384, 2048, 1536, 4, "swiglu", 12288, 8),
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



def test_the_gathered_rows_cast_inside_their_sum_at_the_lfm2_cells_shape(one_chip, no_compile_cache):
    """The combine of the lfm2 cell's bucket (12,288 rows over 2 x 8,192
    tokens, 4 a token) by gathers through the inverse sort, added to its
    input: no float32 ``[T, d]`` is written out alone.  Each choice's rows
    are selected before they are cast; cast first, the compiler wrote every
    choice's float32 copy to memory, 4 x 134 MB a pass."""
    b, s, d, h, top_k, held, rows = 2, 8192, 2048, 1536, 4, 8, 12288
    tokens = b * s
    assert moe_ops._gathers(tokens * top_k, rows, top_k)
    spec = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def layer(x, gate, w_in, w_down, order, group_sizes, n_here):
        y = moe_ops._experts_on_rows(rows, x.reshape(tokens, d), order, gate, group_sizes,
                                     n_here, w_in, w_down, top_k, "swiglu", "pallas")
        return x + y.astype(x.dtype).reshape(x.shape)

    text = jax.jit(layer).lower(
        spec((b, s, d), jnp.bfloat16), spec((tokens * top_k,), jnp.float32),
        spec((held, d, 2 * h), jnp.bfloat16), spec((held, h, d), jnp.bfloat16),
        spec((tokens * top_k,), jnp.int32), spec((held,), jnp.int32),
        spec((), jnp.int32)).compile().as_text()
    unfused = [c for c in text.split("\n\n") if "fused_computation" not in c.split("\n", 1)[0]]
    assert re.findall(r"= f32\[[0-9,]+\]\{[^}]*\} convert\(", "\n".join(unfused)) == []

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


def test_the_grouped_query_kernels_compile_at_the_solar_cells_shapes(one_chip, no_compile_cache):
    """The blockwise forward and one-pass backward with 8 query heads on ONE
    key/value head of 128 at S 4096, bf16, 512 x 512 blocks: this chip's
    share of the gated GQA layer."""
    s, scale = 4096, 128 ** -0.5
    spec = lambda shape: jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)
    launch = attn_ops._Launch(False, (512, 512))

    def loss(q, k, v):
        out, lse = attn_ops._flash_kernels_lse(q, k, v, True, scale, launch)
        return out.astype(jnp.float32).sum(), lse

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2), has_aux=True)).lower(
        spec((1, 8, s, 128)), spec((1, 1, s, 128)), spec((1, 1, s, 128))).compile()
    assert compiled.as_text().count('custom_call_target="tpu_custom_call"') == 2


def test_the_grouped_query_kernels_compile_at_head_width_64_for_the_lfm2_cell(
        one_chip, no_compile_cache):
    """The blockwise forward and one-pass backward at the lfm2 cell's shapes:
    2 rows of 32 query heads on 8 key/value heads (groups of 4) of head width
    64 at S 8192, bf16, 512 x 512 blocks: blocks half a 128-lane column wide,
    which the one-tile kernels of BERT's width 64 never hand the blockwise
    ones."""
    s, scale = 8192, 64 ** -0.5
    spec = lambda shape: jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)
    launch = attn_ops._Launch(False, (512, 512))
    assert attn_ops._pallas_blocks(s, s) == (512, 512)

    def loss(q, k, v):
        out, lse = attn_ops._flash_kernels_lse(q, k, v, True, scale, launch)
        return out.astype(jnp.float32).sum(), lse

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2), has_aux=True)).lower(
        spec((2, 32, s, 64)), spec((2, 8, s, 64)), spec((2, 8, s, 64))).compile()
    assert compiled.as_text().count('custom_call_target="tpu_custom_call"') == 2


def test_the_scan_kernels_compile_at_the_nemotron_cells_shape(one_chip, no_compile_cache):
    """The chunked scan's forward and backward kernels at the nemotron cell's
    shape: x̃ [1, 8192, 64, 64] bf16 as [1, 8192, 4096], 8 groups of state
    128, chunk 128 (the grid cell's [128, 512] x̃ columns sliced a 64-lane head
    at a time, D along the lanes, the running sums turned into columns, the
    entering states written as the forward rule's residual: what an
    interpreter does not check).  The forward alone is one Mosaic call, the
    gradient of a scalar that needs y two, and on this CPU the predicate
    keeps the XLA chunks."""
    b, s, h, p, g, n, q = 1, 8192, 64, 64, 8, 128, 128
    spec = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    x, bc = spec((b, s, h * p), jnp.bfloat16), spec((b, s, g * n), jnp.bfloat16)
    dl, d = spec((b, s // q, h, q), jnp.float32), spec((b, h, 128), jnp.float32)
    launch = attn_ops._Launch(False)
    assert ssm_ops._scan_path(jax.ShapeDtypeStruct((b, s, h, p), jnp.bfloat16),
                              jax.ShapeDtypeStruct((b, s, g, n), jnp.bfloat16), q) is None

    forward = jax.jit(lambda *a: ssm_ops._scan_kernels(*a, g, launch)).lower(
        x, dl, dl, bc, bc, d).compile()
    assert forward.as_text().count('custom_call_target="tpu_custom_call"') == 1

    def loss(*a):
        return ssm_ops._scan_kernels(*a, g, launch).astype(jnp.float32).sum()

    text = jax.jit(jax.grad(loss, argnums=tuple(range(6)))).lower(
        x, dl, dl, bc, bc, d).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    assert "ssm_scan_fwd" in text and "ssm_scan_bwd" in text


# cell: batch, query heads, key/value heads, length, key width, value width, selected
FOLDED_ATTENTION = {
    "keye-vl-2.0-30b-a3b.clm-s8192": (1, 32, 4, 8192, 128, 128, True),
    "xing4.0-29b-a4b.clm-s4096": (1, 32, 32, 4096, 192, 128, False),
    "lfm2-24b-a2b.clm-b2-s8192": (2, 32, 8, 8192, 64, 64, False),
    "nemotron-3-nano-30b-a3b.clm-s8192": (1, 32, 2, 8192, 128, 128, False),
}


@pytest.mark.parametrize("cell", FOLDED_ATTENTION)
def test_the_folded_causal_kernels_compile_at_the_decoder_cells_shapes(
        cell, one_chip, no_compile_cache):
    """The causal blockwise forward (with its log-sum-exp) and one-pass
    backward at each decoder cell's shapes, bf16, 512 x 512 blocks: an even
    number of blocks tiles the square score matrix, so both are the FOLDED
    kernels, whose cells keep a head's rows (forward) or keys (backward)
    resident in VMEM and read a block pair a step at offsets the step index
    gives: what an interpreter does not check."""
    b, h, h_kv, s, d, d_v, selected = FOLDED_ATTENTION[cell]
    spec = lambda shape, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    launch = attn_ops._Launch(False, (512, 512))
    assert attn_ops._pallas_blocks(s, s) == (512, 512)
    assert attn_ops._folds(True, s, s, (512, 512), d, d_v, jnp.bfloat16, h // h_kv, selected)

    def loss(q, k, v, *select):
        out, lse = attn_ops._flash_kernels_lse(q, k, v, True, d ** -0.5, launch, *select)
        return out.astype(jnp.float32).sum(), lse

    operands = (spec((b, h, s, d)), spec((b, h_kv, s, d)), spec((b, h_kv, s, d_v)))
    operands += (spec((b, s, s), jnp.int8),) if selected else ()
    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2), has_aux=True)).lower(
        *operands).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    assert "flash_fwd_folded" in text and "flash_bwd_folded" in text


@pytest.mark.parametrize("kernel", ["forward", "backward"])
def test_the_folded_kernels_code_does_not_grow_with_the_trip_count(
        kernel, one_chip, no_compile_cache):
    """The folded kernels at S 4096 and S 8192 with 512 x 512 blocks (9 and 17
    steps a grid cell), at the keye cell's heads under its int8 selection:
    the executable's generated code (``memory_analysis``; the Mosaic
    kernel's bundles are in it) is no larger at 17 steps than at 9 within a
    tenth, where an unrolled loop's grows with the steps (1.77 x the
    forward's, 1.43 x the backward's).  Two steps a loop body and the one
    left over are three copies of the step whatever the count."""
    spec = lambda shape, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    scale = 128 ** -0.5

    def code_bytes(s):
        select = spec((1, s, s), jnp.int8)
        if kernel == "forward":
            fn = lambda q, k, v, select: attn_ops._flash_fwd_pallas(
                q, k, v, True, scale, False, 512, 512, with_lse=True, select=select)
            operands = (spec((32, s, 128)), spec((4, s, 128)), spec((4, s, 128)), select)
        else:
            fn = lambda q, k, v, do, o, lse, select: attn_ops._flash_bwd_pallas(
                q, k, v, do, o, lse, True, scale, False, 512, 512, select=select)
            operands = (spec((32, s, 128)), spec((4, s, 128)), spec((4, s, 128)),
                        spec((32, s, 128)), spec((32, s, 128)), spec((32, s), jnp.float32), select)
        compiled = jax.jit(fn).lower(*operands).compile()
        assert ("flash_fwd_folded" if kernel == "forward" else "flash_bwd_folded") in compiled.as_text()
        return compiled.memory_analysis().generated_code_size_in_bytes

    assert code_bytes(8192) <= 1.1 * code_bytes(4096)


@pytest.mark.parametrize("s, folds", [(16384, True), (32768, False)])
def test_long_causal_calls_compile_folded_only_where_the_rows_fit(s, folds, one_chip,
                                                                  no_compile_cache):
    """tools/bench_longcontext.py's causal shapes, 8 heads of 64, bf16, 512 x
    512 blocks, value and gradient: at S 16384 the folded kernels' resident
    rows fit in VMEM; at S 32768 the folded backward's would not (the
    compiler refuses it), so the call keeps the unfolded kernels."""
    spec = lambda shape: jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)
    launch = attn_ops._Launch(False, (512, 512))

    def loss(q, k, v):
        return attn_ops._flash_kernels(q, k, v, True, 0.125, launch).astype(jnp.float32).sum()

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        *(spec((1, 8, s, 64)),) * 3).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    assert ("flash_fwd_folded" in text) == folds and ("flash_bwd_folded" in text) == folds
