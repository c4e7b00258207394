"""Manifold-constrained hyper-connections (mHC): a residual path of ``n``
streams that every sublayer reads through one learned per-token mix and
writes back through two more.

Per token the residual state is ``X`` [n, d].  For a sublayer ``F``:

    x̃ = vec(X);  m = (W_hc x̃) / sqrt(mean(x̃²) + eps)          [n² + 2n]
    H_pre  = σ(α_pre · m[0:n] + b_pre)                          [n]
    H_post = 2 σ(α_post · m[n:2n] + b_post)                     [n]
    H_res  = SK(clip(α_res · mat(m[2n:]) + b_res, lo, hi))      [n, n]
    u = H_preᵀ X;   y = F(u);   X' = H_res X + H_post ⊗ y

``SK`` is Sinkhorn-Knopp on ``exp(A)``: ``iters`` times, divide the rows by
their sums, then the columns by theirs (``hc_eps`` in each divisor), which
leaves a matrix that is nearly doubly stochastic: the mix of the streams
neither grows nor shrinks the state.

Two registered ops, :func:`mhc_pre` (coefficients and ``u``) and
:func:`mhc_post` (``X'``).  The coefficient path is float32 whatever the
state's dtype: ``W_hc x̃`` multiplies in the state's dtype (the products of
two bf16 numbers are exact in float32) and accumulates in float32, and
everything after it is float32.  The two passes over the state (``u`` and
``X'``) are memory-bound: n·d numbers a token read twice and written once.

Layouts, chosen for the TPU's (8, 128) tiles.  The state is STREAM-MAJOR,
``[n, ..., d]``: stream i is ``state[i]``, a whole ``[..., d]`` array whose
tiles are full.  As ``[..., n, d]`` the n = 4 streams would sit in the
sublanes of a tile and leave three quarters of every bf16 tile empty, and as
``[..., n·d]`` every pass slices and concatenates lanes (measured on the
v5e, PERF.md PR 28: 1.8 ms a pass over 117 MB, six times the memory's
speed).  The coefficients are coefficient-major too, ``H_post`` ``[n, ...]``
and ``H_res`` ``[n, n, ...]`` with the tokens LAST: Sinkhorn's forty tiny
normalisations then run over arrays whose lanes are tokens, not over one
padded tile a token.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .registry import register

__all__ = ["sinkhorn", "mhc_pre", "mhc_post"]


def sinkhorn(logits, iters=20, eps=1e-6, axes=(-1, -2)):
    """``SK(A)`` (float32): ``exp``, then ``iters`` times the rows divided by
    their sums and the columns by theirs.  ``axes`` = (the axis a row runs
    along, the axis a column runs along): ``(-1, -2)`` for ``[..., n, n]``,
    ``(1, 0)`` for the coefficient-major ``[n, n, ...]``."""
    m = jnp.exp(logits.astype(jnp.float32))
    for _ in range(int(iters)):
        m = m / (m.sum(axes[0], keepdims=True) + eps)
        m = m / (m.sum(axes[1], keepdims=True) + eps)
    return m


@register("mhc_pre")
def mhc_pre(state, w_hc, alpha, offset, sinkhorn_iters=20, eps=1e-6,
            hc_eps=1e-6, clamp_min=-30.0, clamp_max=30.0, scope="mhc"):
    """``state`` [n, ..., d] (stream-major); ``w_hc`` [n² + 2n, n·d] over
    ``vec(X)`` (stream i's columns are ``i·d : (i+1)·d``); ``alpha`` [3]
    (pre, post, res); ``offset`` [n² + 2n] (b_pre | b_post | b_res
    row-major).  Returns ``(u [..., d] in the state's dtype, H_post [n, ...]
    float32, H_res [n, n, ...] float32)``."""
    n, d = state.shape[0], state.shape[-1]
    lead = state.shape[1:-1]
    f32 = jnp.float32
    with jax.named_scope(scope + ".pre"):
        prec = (jax.lax.Precision.HIGHEST if state.dtype == f32
                else jax.lax.Precision.DEFAULT)
        w = w_hc.astype(state.dtype)
        proj = sum(jnp.einsum("oi,...i->o...", w[:, i * d:(i + 1) * d],
                              state[i], precision=prec,
                              preferred_element_type=f32) for i in range(n))
        x32 = [state[i].astype(f32) for i in range(n)]
        ms = sum(jnp.sum(jnp.square(x), axis=-1) for x in x32) / (n * d)
        m = proj * jax.lax.rsqrt(ms + eps)                     # [n²+2n, ...]
        a = alpha.astype(f32)
        b = offset.astype(f32).reshape((-1,) + (1,) * len(lead))
        h_pre = jax.nn.sigmoid(a[0] * m[:n] + b[:n])
        h_post = 2.0 * jax.nn.sigmoid(a[1] * m[n:2 * n] + b[n:2 * n])
        res = (a[2] * m[2 * n:] + b[2 * n:]).reshape((n, n) + lead)
    with jax.named_scope(scope + ".sinkhorn"):
        h_res = sinkhorn(jnp.clip(res, clamp_min, clamp_max),
                         sinkhorn_iters, hc_eps, axes=(1, 0))
    with jax.named_scope(scope + ".pre"):
        # n multiply-adds an element, written out: a contraction over n = 4
        # is no work for the MXU, and as one elementwise fusion the pass
        # stays at the memory's speed
        u = sum(h_pre[i][..., None] * x32[i] for i in range(n))
    return u.astype(state.dtype), h_post, h_res


@register("mhc_post")
def mhc_post(state, y, h_post, h_res, scope="mhc"):
    """``X' = H_res X + H_post ⊗ y`` in float32, stored in the state's
    dtype.  ``state`` [n, ..., d] stream-major; ``y`` [..., d]; the
    coefficients as :func:`mhc_pre` returns them."""
    f32 = jnp.float32
    n = state.shape[0]
    with jax.named_scope(scope + ".post"):
        x32 = [state[i].astype(f32) for i in range(n)]
        y32 = y.astype(f32)
        streams = []
        for i in range(n):
            out = h_post[i][..., None] * y32
            for m in range(n):
                out = out + h_res[i, m][..., None] * x32[m]
            streams.append(out.astype(state.dtype))
        return jnp.stack(streams, axis=0)
