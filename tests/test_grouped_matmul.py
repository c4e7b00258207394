"""The held experts' grouped products by the Pallas kernels of
``ops/grouped_matmul.py``, in the Pallas interpreter (the kernel functions'
own ``interpret=True``), against a dense per-group loop in float32; and the
dispatch of ``moe_ffn_dropless`` between them and ``jax.lax.ragged_dot``."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from incubator_mxnet_tpu import profiler
from incubator_mxnet_tpu.ops import grouped_matmul as gm
from incubator_mxnet_tpu.ops import moe as moe_ops


def dense_loop(lhs, rhs, sizes):
    """``out[r] = lhs[r] @ rhs[group of r]``; rows past the last group zero."""
    out, at = np.zeros((lhs.shape[0], rhs.shape[2]), np.float32), 0
    for g, size in enumerate(sizes):
        out[at:at + size] = lhs[at:at + size] @ rhs[g]
        at += size
    return out


def dense_loop_wgrad(lhs, rhs, sizes):
    out, at = np.zeros((len(sizes), lhs.shape[1], rhs.shape[1]), np.float32), 0
    for g, size in enumerate(sizes):
        out[g] = lhs[at:at + size].T @ rhs[at:at + size]
        at += size
    return out


# rows, k, n, group sizes, tiles: widths a 128-lane tile divides and does not
# (232 = 1.8125 x 128, the 1856 of the relu2 experts in small), as the
# contraction and as the result's width; an empty group; every row in one
# group; groups that share a row tile and end on its edge; rows short of the
# bucket; no row at all
CASES = {
    "relu2_up_width_232": (256, 256, 232, [40, 0, 100, 30], (128, 256, 232)),
    "relu2_down_contraction_232": (256, 232, 256, [40, 0, 100, 30], (128, 232, 256)),
    "contraction_232_masked_edge_tile": (256, 232, 256, [40, 0, 100, 30], (128, 128, 128)),
    "width_232_edge_tile": (256, 256, 232, [37, 91, 0, 128], (128, 128, 128)),
    "swiglu_gate_up_width_256": (384, 128, 256, [128, 128, 1, 127], (128, 128, 256)),
    "swiglu_down": (384, 128, 256, [100, 60, 90, 70], (128, 128, 128)),
    "all_rows_in_one_group": (256, 128, 128, [0, 256, 0, 0], (256, 128, 128)),
    "one_group_short_of_the_bucket": (256, 128, 232, [0, 0, 0, 150], (128, 128, 232)),
    "no_row_at_all": (256, 128, 128, [0, 0, 0, 0], (128, 128, 128)),
    "many_row_tiles_a_group": (1024, 128, 128, [300, 500, 0, 3], (128, 128, 128)),
    "three_column_chunks_of_the_result": (256, 128, 600, [60, 0, 100, 90], (128, 128, 600)),
    "three_column_chunks_and_two_contraction_tiles": (256, 600, 640, [60, 0, 100, 90], (128, 384, 640)),
}


def operands(case, seed=0):
    rows, k, n, sizes, tiles = CASES[case]
    rng = np.random.default_rng(seed)
    lhs = rng.standard_normal((rows, k)).astype(np.float32)
    rhs = rng.standard_normal((len(sizes), k, n)).astype(np.float32)
    ct = rng.standard_normal((rows, n)).astype(np.float32)
    return lhs, rhs, ct, np.asarray(sizes, np.int32), tiles


def planted(x, n_here):
    """NaN in the rows past the last group: nothing may read them."""
    x = x.copy()
    x[n_here:] = np.nan
    return jnp.asarray(x)


@pytest.mark.parametrize("case", CASES)
def test_forward_product_matches_a_dense_loop(case):
    lhs, rhs, _, sizes, tiles = operands(case)
    n_here = int(sizes.sum())
    out = np.asarray(gm.grouped_matmul(planted(lhs, n_here), jnp.asarray(rhs),
                                       jnp.asarray(sizes), tiles=tiles, interpret=True))
    np.testing.assert_allclose(out[:n_here], dense_loop(lhs, rhs, sizes)[:n_here],
                               rtol=1e-5, atol=1e-4)
    # rows past the last group: zero in the tiles a group visited
    visited = -(-n_here // tiles[0]) * tiles[0]
    assert not out[n_here:visited].any()


@pytest.mark.parametrize("case", CASES)
def test_input_gradient_product_matches_a_dense_loop(case):
    lhs, rhs, ct, sizes, (tm, tk, tn) = operands(case)
    n_here = int(sizes.sum())
    out = np.asarray(gm.grouped_matmul(
        planted(ct, n_here), jnp.asarray(rhs), jnp.asarray(sizes), tiles=(tm, tn, tk),
        transpose_rhs=True, interpret=True))
    want = dense_loop(ct, rhs.transpose(0, 2, 1), sizes)
    np.testing.assert_allclose(out[:n_here], want[:n_here], rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("case", CASES)
def test_weight_gradient_product_matches_a_dense_loop(case):
    lhs, rhs, ct, sizes, tiles = operands(case)
    n_here = int(sizes.sum())
    out = np.asarray(gm.grouped_matmul_wgrad(
        planted(lhs, n_here), planted(ct, n_here), jnp.asarray(sizes), tiles=tiles,
        interpret=True))
    np.testing.assert_allclose(out, dense_loop_wgrad(lhs, ct, sizes), rtol=1e-5, atol=2e-4)
    assert not out[sizes == 0].any()        # an empty group's block is written, as zeros


@pytest.mark.parametrize("case", ["relu2_up_width_232", "relu2_down_contraction_232",
                                  "swiglu_down", "no_row_at_all"])
def test_grouped_dot_differentiates_by_the_other_two_kernels(case):
    """``grouped_dot`` is ``ragged_dot`` with its own rule: value and both
    gradients equal XLA's on the rows that are some group's, at the tiles
    the package picks."""
    lhs, rhs, ct, sizes, _ = operands(case)
    n_here = int(sizes.sum())
    ours = (np.arange(lhs.shape[0]) < n_here)[:, None]
    lhs, ct = np.where(ours, lhs, 0), np.where(ours, ct, 0)
    args = jnp.asarray(lhs), jnp.asarray(rhs)
    got, pull = jax.vjp(lambda a, w: gm.grouped_dot(a, w, jnp.asarray(sizes), True), *args)
    want, pull_xla = jax.vjp(lambda a, w: jax.lax.ragged_dot(
        a, w, jnp.asarray(sizes), precision=jax.lax.Precision.HIGHEST), *args)
    np.testing.assert_allclose(np.where(ours, got, 0), want, rtol=1e-5, atol=1e-4)
    (da, dw), (da_xla, dw_xla) = pull(jnp.asarray(ct)), pull_xla(jnp.asarray(ct))
    np.testing.assert_allclose(np.where(ours, da, 0), da_xla, rtol=1e-5, atol=1e-4)
    np.testing.assert_allclose(dw, dw_xla, rtol=1e-5, atol=2e-4)
    assert dw.dtype == rhs.dtype and da.dtype == lhs.dtype


@pytest.mark.parametrize("form,n_here", [("relu2", 100), ("swiglu", 100), ("relu2", 0),
                                         ("swiglu", 128)])
def test_rows_past_the_routed_ones_are_zero_in_result_and_cotangent(form, n_here):
    """The contract ``grouped()`` documents, on the kernels: with ``n_here``
    short of the 128-row bucket, the layer's value and every gradient equal
    the XLA path's, and tokens none of whose pairs is ours get a zero row and
    a zero cotangent — the kernels leave the rows past ``n_here`` undefined
    (NaN in the interpreter) and the masks keep them out."""
    rng = np.random.default_rng(4)
    tokens, d, h, top_k = 64, 64, 232, 2
    sizes = {0: [0, 0, 0, 0], 100: [20, 0, 50, 30], 128: [0, 128, 0, 0]}[n_here]
    xt = jnp.asarray(rng.standard_normal((tokens, d)), jnp.float32)
    w_in = jnp.asarray(rng.standard_normal((4, d, h * (2 if form == "swiglu" else 1))) * 0.1,
                       jnp.float32)
    w_down = jnp.asarray(rng.standard_normal((4, h, d)) * 0.1, jnp.float32)
    gate = jnp.asarray(rng.uniform(size=(tokens * top_k,)), jnp.float32)
    order = jnp.asarray(rng.permutation(tokens * top_k), jnp.int32)
    weigh = jnp.cos(jnp.arange(tokens * d, dtype=jnp.float32)).reshape(tokens, d)

    def run(path):
        def loss(xt, gate, w_in, w_down):
            y = moe_ops._experts_on_rows(128, xt, order, gate, jnp.asarray(sizes, jnp.int32),
                                         jnp.int32(n_here), w_in, w_down, top_k, form, path)
            return (y * weigh).sum(), y
        return jax.value_and_grad(loss, argnums=(0, 1, 2, 3), has_aux=True)(
            xt, gate, w_in, w_down)

    ((_, y), grads), ((_, y_xla), grads_xla) = run("interpret"), run("xla")
    np.testing.assert_allclose(y, y_xla, rtol=1e-5, atol=1e-5)
    for got, want in zip(grads, grads_xla):
        assert np.isfinite(np.asarray(got)).all()
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=2e-5)
    untouched = np.setdiff1d(np.arange(tokens), np.asarray(order)[:n_here] // top_k)
    assert not np.asarray(y)[untouched].any() and not np.asarray(grads[0])[untouched].any()


def test_moe_ffn_dropless_traces_onto_ragged_dot_off_the_tpu():
    """On the CPU the dispatcher keeps ``jax.lax.ragged_dot`` — in the forward
    and in the backward's branches — and counts the call site once."""
    rng = np.random.default_rng(1)
    e, d, h = 8, 32, 16
    args = (jnp.asarray(rng.standard_normal((2, 24, d)), jnp.float32),
            jnp.asarray(rng.standard_normal((e, d)), jnp.float32), jnp.zeros((e,)),
            jnp.asarray(rng.standard_normal((2, d, h)), jnp.float32),
            jnp.asarray(rng.standard_normal((2, h, d)), jnp.float32))

    def layer(x, router, bias, w_in, w_down):
        return moe_ops.moe_ffn_dropless(x, router, bias, w_in, w_down, num_experts=e, top_k=2,
                                        first_expert=2, expert_form="relu2")[0].sum()

    before = profiler.counters()
    text = str(jax.make_jaxpr(jax.grad(layer, argnums=(0, 3, 4)))(*args))
    counted = {name: profiler.counters()[name] - before[name]
               for name in ("moe_grouped_dispatch_xla", "moe_grouped_dispatch_pallas")}
    assert counted == {"moe_grouped_dispatch_xla": 1, "moe_grouped_dispatch_pallas": 0}
    assert "ragged_dot" in text and "pallas_call" not in text


@pytest.mark.parametrize("buckets,rows,path", [
    ((96, 4608, 9216, 49152), 4608, "pallas"), ((96, 4608, 9216, 49152), 9216, "pallas"),
    ((96, 4608, 9216, 49152), 49152, "xla"),        # every pair, beyond 3 x the expected share
    ((64, 3072, 6144, 16384), 16384, "xla"), ((8, 48), 48, "pallas"),   # every expert held here
    ((8, 72, 96), 96, "pallas")])                  # every pair is within 3 x the share
def test_the_worst_case_bucket_keeps_xla_where_smaller_buckets_take_the_kernels(buckets, rows, path):
    assert moe_ops._path_in(buckets, rows, "pallas") == path
    assert moe_ops._path_in(buckets, rows, "xla") == "xla"


@pytest.mark.parametrize("rows,groups,tile", [
    (4608, 8, 256), (9216, 8, 256), (49152, 8, 256), (3072, 8, 128), (6144, 8, 256),
    (16384, 8, 256), (384, 1, 128), (768, 8, 128), (96, 8, None), (64, 8, None), (8, 2, None)])
def test_row_tile_divides_the_bucket_or_leaves_it_to_xla(rows, groups, tile):
    """Both decoder cells' buckets (``dropless_row_buckets``: 96 / 4608 / 9216
    / 49152 and 64 / 3072 / 6144 / 16384) but the smallest take a kernel
    tile: 256 rows, or 128 where that is more than half the mean group."""
    assert gm.row_tile(rows, groups) == tile


@pytest.mark.parametrize("shape,wgrad,tiles", [
    ((4608, 2688, 1856, 8, 2), False, (256, 2688, 1856)),     # the nemotron cell, bf16
    ((4608, 1856, 2688, 8, 2), True, (256, 1856, 2688)),
    ((3072, 3584, 2048, 8, 2), False, (128, 3584, 2048)),     # the xing cell, bf16
    ((3072, 3584, 2048, 8, 2), True, (128, 3584, 2048)),
    ((3072, 3584, 2048, 8, 4), True, (128, 1792, 2048)),      # float32: the result's rows halved
    ((3072, 8192, 4096, 8, 2), False, (128, 8192, 2048)),     # a weight VMEM does not hold twice
])
def test_tiles_keep_a_groups_whole_matrix_where_vmem_holds_it(shape, wgrad, tiles):
    assert gm._pick_tiles(*shape, wgrad=wgrad) == tiles
