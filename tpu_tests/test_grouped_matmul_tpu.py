"""The COMPILED grouped-product kernels (``ops/grouped_matmul.py``) on the
chip, in bf16 at both decoder cells' shapes, against a dense per-group loop
in float32 on the host; and ``moe_ffn_dropless`` taking them on the TPU.

    MXNET_TEST_CTX=tpu python -m pytest tpu_tests/test_grouped_matmul_tpu.py -q
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from incubator_mxnet_tpu import profiler
from incubator_mxnet_tpu.ops import grouped_matmul as gm
from incubator_mxnet_tpu.ops import moe as moe_ops

# cell and product: rows of the bucket, rows routed, k, n
SHAPES = {
    "nemotron_up": (4608, 3072, 2688, 1856),
    "nemotron_down": (4608, 3072, 1856, 2688),
    "xing_gate_up": (3072, 2050, 3584, 2048),
    "xing_down": (3072, 2050, 1024, 3584),
}
GROUPS = 8


def operands(shape, seed=0):
    rows, routed, k, n = SHAPES[shape]
    rng = np.random.default_rng(seed)
    sizes = rng.multinomial(routed, np.full(GROUPS, 1 / GROUPS)).astype(np.int32)
    sizes[3] += sizes[5]
    sizes[5] = 0                                       # one empty group
    bf = lambda x: np.array(jnp.asarray(x, jnp.bfloat16).astype(jnp.float32))   # a copy: writable
    lhs = bf(rng.standard_normal((rows, k)))
    rhs = bf(rng.standard_normal((GROUPS, k, n)) * 0.02)
    ct = bf(rng.standard_normal((rows, n)))
    lhs[routed:], ct[routed:] = 0, 0
    return lhs, rhs, ct, sizes


def close(got, want):
    """bf16 results of float32 sums: a result rounds to 2^-9 of itself."""
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    scale = np.sqrt(np.mean(want ** 2))
    assert np.sqrt(np.mean((got - want) ** 2)) < 3e-3 * scale
    assert np.abs(got - want).max() < 2 ** -7 * np.abs(want).max() + 1e-3 * scale


@pytest.mark.parametrize("shape", SHAPES)
def test_compiled_kernels_match_a_dense_loop(shape):
    lhs, rhs, ct, sizes = operands(shape)
    routed = int(sizes.sum())
    at = np.concatenate([[0], np.cumsum(sizes)])
    args = jnp.asarray(lhs, jnp.bfloat16), jnp.asarray(rhs, jnp.bfloat16)
    got, pull = jax.vjp(lambda a, w: gm.grouped_dot(a, w, jnp.asarray(sizes)), *args)
    da, dw = pull(jnp.asarray(ct, jnp.bfloat16))
    assert got.dtype == da.dtype == dw.dtype == jnp.bfloat16
    want = np.zeros((lhs.shape[0], rhs.shape[2]), np.float32)
    want_da, want_dw = np.zeros_like(lhs), np.zeros_like(rhs)
    for g in range(GROUPS):
        rows = slice(at[g], at[g + 1])
        want[rows] = lhs[rows] @ rhs[g]
        want_da[rows] = ct[rows] @ rhs[g].T
        want_dw[g] = lhs[rows].T @ ct[rows]
    close(np.asarray(got, np.float32)[:routed], want[:routed])
    close(np.asarray(da, np.float32)[:routed], want_da[:routed])
    close(dw, want_dw)
    assert not np.asarray(dw, np.float32)[5].any()


@pytest.mark.parametrize("form", ["relu2", "swiglu"])
def test_moe_ffn_dropless_takes_the_kernels_on_the_tpu(form, monkeypatch):
    """Value and gradients of the layer on the chip (kernels, bf16) against
    the same layer through ``ragged_dot``; one call site counted."""
    rng = np.random.default_rng(2)
    e, held, d, h, tokens = 32, 8, 256, 232, 1024
    w = lambda *s: jnp.asarray(rng.standard_normal(s) * 0.05, jnp.bfloat16)
    x, router = w(tokens, d) * 20, jnp.asarray(rng.standard_normal((e, d)), jnp.float32)
    w_in, w_down = w(held, d, h * (2 if form == "swiglu" else 1)), w(held, h, d)

    def step():
        def layer(x, w_in, w_down):
            y, rows = moe_ops.moe_ffn_dropless(
                x, router, jnp.zeros((e,)), w_in, w_down, num_experts=e, top_k=4,
                first_expert=8, expert_form=form)[:2]
            return (y.astype(jnp.float32) ** 2).sum(), rows
        return jax.jit(jax.value_and_grad(layer, argnums=(0, 1, 2), has_aux=True))(
            x, w_in, w_down)

    before = profiler.counters()["moe_grouped_dispatch_pallas"]
    (loss, rows), grads = step()
    assert profiler.counters()["moe_grouped_dispatch_pallas"] == before + 1
    monkeypatch.setattr(moe_ops, "_grouped_path", lambda x: "xla")
    (loss_xla, rows_xla), grads_xla = step()
    assert float(rows) == float(rows_xla) > 0
    np.testing.assert_allclose(float(loss), float(loss_xla), rtol=2e-2)
    for got, want in zip(grads, grads_xla):
        got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
        assert np.isfinite(got).all()
        assert np.sqrt(np.mean((got - want) ** 2)) < 2e-2 * np.sqrt(np.mean(want ** 2))


# cell: tokens, model width, expert width, experts a token, the row bucket the cell runs in
LAYERS = {
    "xing4.0-29b-a4b.clm-s4096": (4096, 3584, 1024, 4, 3072),
    "lfm2-24b-a2b.clm-b2-s8192": (16384, 2048, 1536, 4, 12288),
}


@pytest.mark.parametrize("cell", LAYERS)
def test_gathers_and_scatter_adds_give_one_layer_on_the_chip(cell, monkeypatch):
    """One expert layer's bucket at the cell's shapes on the kernels, rows
    moved by gathers through the inverse sort and by scatter-adds: the same
    value and the same gradients (the input gradient summed in float32 by the
    gathers and in bf16 by the scatter-add: a bf16 rounding apart)."""
    tokens, d, h, top_k, rows = LAYERS[cell]
    rng = np.random.default_rng(5)
    key = np.minimum(rng.integers(0, 64, tokens * top_k), GROUPS)      # 8 of 64 held
    order = np.argsort(key, kind="stable").astype(np.int32)
    sizes = np.bincount(key, minlength=GROUPS + 1)[:GROUPS].astype(np.int32)
    assert sizes.sum() <= rows
    w = lambda *s: jnp.asarray(rng.standard_normal(s) * 0.02, jnp.bfloat16)
    xt, w_in, w_down = w(tokens, d) * 50, w(GROUPS, d, 2 * h), w(GROUPS, h, d)
    gate = jnp.asarray(rng.uniform(size=tokens * top_k), jnp.float32)
    consts = jnp.asarray(order), jnp.asarray(sizes), jnp.int32(sizes.sum())

    def run(ratio):
        monkeypatch.setattr(moe_ops, "GATHER_RATIO", ratio)
        assert moe_ops._gathers(tokens * top_k, rows, top_k) == (ratio > 0)

        def loss(xt, gate, w_in, w_down, order, sizes, n_here):
            y = moe_ops._experts_on_rows(rows, xt, order, gate, sizes, n_here, w_in, w_down,
                                         top_k, "swiglu", "pallas")
            return (y * y).sum(), y
        return jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2, 3), has_aux=True))(
            xt, gate, w_in, w_down, *consts)

    (_, y), grads = run(1e9)
    (_, y_scatter), grads_scatter = run(0)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_scatter), rtol=1e-4, atol=1e-4)
    for got, want in zip(grads, grads_scatter):
        assert np.isfinite(np.asarray(got, np.float32)).all()
        close(got, want)
