"""The folded causal blockwise kernels (``ops/attention.py``): a causal call
whose blocks tile a square score matrix with an even number n of blocks takes
them, grid cell i computing query blocks i and n-1-i (the backward: key blocks
j and n-1-j) in one loop of n + 1 steps.  Through the Pallas interpreter at
small shapes: the folded forward gives the unfolded kernel's output and
log-sum-exp bit for bit, the gradients fall within float32 rounding of the
unfolded kernel's (only dq's sums change order) and both are
``attention_reference``'s; every other call keeps the unfolded kernels, which
``attention_dispatch_folded`` and the kernels' names show."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from incubator_mxnet_tpu import profiler
from incubator_mxnet_tpu.ops import attention as attn

# batch, query heads, key/value heads, length, key width, value width, block, selected, dtype
CASES = {
    "n2": (1, 2, 2, 64, 32, 32, 32, False, jnp.float32),
    "n2-selected-grouped": (2, 2, 1, 64, 64, 64, 32, True, jnp.float32),
    "n4-selected-grouped": (1, 4, 2, 128, 32, 32, 32, True, jnp.float32),
    "n4-head64-grouped-batch2": (2, 4, 1, 128, 64, 64, 32, False, jnp.float32),
    "n8-d48-dv32": (1, 2, 2, 256, 48, 32, 32, False, jnp.float32),
    "n8-selected-grouped-dv16": (1, 4, 1, 256, 32, 16, 32, True, jnp.float32),
    "n4-bfloat16-d96-dv64": (1, 2, 2, 128, 96, 64, 32, False, jnp.bfloat16),
}


def _operands(case):
    b, h, h_kv, s, d, d_v, block, selected, dtype = CASES[case]
    ks = jax.random.split(jax.random.PRNGKey(s + 7 * h + d), 5)
    q = jax.random.normal(ks[0], (b, h, s, d), dtype)
    k = jax.random.normal(ks[1], (b, h_kv, s, d), dtype)
    v = jax.random.normal(ks[2], (b, h_kv, s, d_v), dtype)
    w = jax.random.normal(ks[3], (b, h, s, d_v), jnp.float32)
    select = None
    if selected:   # every query its own key at least, so every row has a live key
        select = (jax.random.bernoulli(ks[4], 0.3, (b, s, s))
                  | jnp.eye(s, dtype=bool)).astype(jnp.int8)
    return q, k, v, w, select, block


def _value_and_grads(q, k, v, w, select, block):
    """Output, log-sum-exp and (dq, dk, dv) of ``sum(out · w)`` through the
    blockwise kernels in the interpreter."""
    launch = attn._Launch(True, (block, block))
    scale = q.shape[-1] ** -0.5

    def f(q, k, v):
        return attn._flash_kernels_lse(q, k, v, True, scale, launch, select)

    (out, lse), pull = jax.vjp(f, q, k, v)
    grads = pull((w.astype(out.dtype), jnp.zeros_like(lse)))
    return out, lse, grads


@pytest.mark.parametrize("case", CASES)
def test_folded_kernels_match_the_unfolded_ones_and_the_reference(case, monkeypatch):
    q, k, v, w, select, block = _operands(case)
    s, dtype = q.shape[2], q.dtype
    assert attn._folds(True, s, s, (block, block), q.shape[-1], v.shape[-1], dtype,
                       q.shape[1] // k.shape[1], select is not None)
    folded = _value_and_grads(q, k, v, w, select, block)
    monkeypatch.setattr(attn, "_folds", lambda *a: False)
    unfolded = _value_and_grads(q, k, v, w, select, block)
    monkeypatch.undo()

    np.testing.assert_array_equal(np.asarray(folded[0], np.float32),
                                  np.asarray(unfolded[0], np.float32))
    np.testing.assert_array_equal(np.asarray(folded[1]), np.asarray(unfolded[1]))
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    for got, want in zip(folded[2], unfolded[2]):
        assert got.shape == want.shape and got.dtype == want.dtype
        np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want, np.float32),
                                   rtol=tol, atol=tol)

    group = q.shape[1] // k.shape[1]
    kk, vv = (jnp.repeat(x, group, axis=1) for x in (k, v))
    scale = q.shape[-1] ** -0.5
    out_ref, pull = jax.vjp(
        lambda q, k, v: attn.attention_reference(q, k, v, True, scale, select), q, kk, vv)
    dq_ref, dk_ref, dv_ref = pull(w.astype(out_ref.dtype))
    b, h_kv = k.shape[:2]
    fold_heads = lambda g: g.reshape((b, h_kv, group) + g.shape[2:]).astype(jnp.float32).sum(2)
    ref_tol = 5e-5 if dtype == jnp.float32 else 5e-2
    for got, want in ((folded[0], out_ref), (folded[2][0], dq_ref),
                      (folded[2][1], fold_heads(dk_ref)), (folded[2][2], fold_heads(dv_ref))):
        np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want, np.float32),
                                   rtol=ref_tol, atol=ref_tol)


def _traced(q, k, v, causal, select=None):
    """Trace value and gradient of ``flash_attention`` in the interpreter:
    the counters' moves, and the names of the kernels in the jaxpr."""
    before = profiler.counters()
    jaxpr = str(jax.make_jaxpr(jax.grad(
        lambda q, k, v: attn.flash_attention(q, k, v, causal, select=select).sum(),
        argnums=(0, 1, 2)))(q, k, v))
    after = profiler.counters()
    moved = {n: after[n] - before[n] for n in
             ("attention_dispatch_pallas", "attention_dispatch_folded")}
    return moved, jaxpr


# query length, key length, causal, folds: the dispatcher's blocks are the
# largest of 512, 256, ... that divides each length
DISPATCH = {
    "causal-square-n2": (1024, 1024, True, True),
    "causal-square-n4": (2048, 2048, True, True),
    "causal-square-odd-n3": (1536, 1536, True, False),
    "causal-not-square": (1024, 2048, True, False),
    "not-causal": (1024, 1024, False, False),
    "causal-square-n32-over-vmem": (16384, 16384, True, False),
}


@pytest.mark.parametrize("case", DISPATCH)
def test_only_causal_square_calls_of_an_even_block_count_fold(case, monkeypatch):
    monkeypatch.setenv("MXNET_TPU_FLASH", "interpret")
    sq, sk, causal, folds = DISPATCH[case]
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(ks[0], (1, 2, sq, 32), jnp.float32)
    k = jax.random.normal(ks[1], (1, 1, sk, 32), jnp.float32)
    v = jax.random.normal(ks[2], (1, 1, sk, 32), jnp.float32)
    path, launch = attn._kernel_path(q, k)
    assert path == "blockwise"
    assert attn._folds(causal, sq, sk, launch.blocks, 32, 32, jnp.float32, 2) == folds
    moved, jaxpr = _traced(q, k, v, causal)
    assert moved == {"attention_dispatch_pallas": 1, "attention_dispatch_folded": int(folds)}
    assert ("flash_fwd_folded" in jaxpr) == folds and ("flash_bwd_folded" in jaxpr) == folds
    assert ("flash_fwd" in jaxpr.replace("flash_fwd_folded", "")) == (not folds)


# length, key width, value width, query heads a key/value head, selected, folds
FITS = {
    "keye": (8192, 128, 128, 8, True, True),
    "xing": (4096, 192, 128, 1, False, True),
    "lfm2": (8192, 64, 64, 4, False, True),
    "nemotron": (8192, 128, 128, 16, False, True),
    "long-s16384-head64": (16384, 64, 64, 1, False, True),
    "long-s32768-head64": (32768, 64, 64, 1, False, False),
    "s16384-keys192": (16384, 192, 128, 1, False, False),
}


@pytest.mark.parametrize("case", FITS)
def test_only_calls_whose_resident_rows_fit_in_vmem_fold(case):
    """bf16, 512 x 512 blocks: the decoder cells' calls fold; a head whose
    rows the folded backward cannot keep in VMEM beside its tiles (S 32768
    with 64-wide heads: 128 MB of blocks; S 16384 with 192-wide keys: 104
    MB) keeps the unfolded kernels."""
    s, d, d_v, group, selected, folds = FITS[case]
    assert attn._folds(True, s, s, (512, 512), d, d_v, jnp.bfloat16, group, selected) == folds
