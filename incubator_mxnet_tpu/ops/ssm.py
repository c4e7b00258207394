"""State-space layers (Mamba-2): the causal depthwise convolution over time,
the chunked state-space scan and the grouped gated RMSNorm, and the mixer
that strings them together (``gluon.model_zoo.nemotron_h`` takes it;
docs/nemotron_h.md has the equations).

The recurrence of one head (``x̃_t`` [P], ``B_t``, ``C_t`` [N], a scalar
step ``Δ_t`` and a scalar decay ``a_t = exp(Δ_t · A)``, ``A < 0``):

    h_t = a_t h_{t−1} + Δ_t · x̃_t ⊗ B_t        (h [P, N], h_0 = 0)
    y_t = h_t C_t + D · x̃_t

:func:`ssm_scan` computes it IN CHUNKS of ``chunk_size`` positions (the
state-space-duality form): with ``cum`` the running sum of ``log a`` inside
a chunk,

* inside a chunk the lower-triangular decay matrix ``L_ij = exp(cum_i −
  cum_j)`` (i ≥ j) and ``y_diag = ((C Bᵀ) ∘ L ∘ Δ_j) x̃`` — two matrix
  products a chunk;
* the chunk's own state ``Σ_j exp(cum_last − cum_j) Δ_j x̃_j ⊗ B_j``;
* the states carried from chunk to chunk by a scan over the S / chunk
  chunks, ``h ← exp(cum_last) h + state``;
* the carried state's part ``y_off,i = exp(cum_i) · C_i h_prev``.

Every exponent is ≤ 0, so nothing overflows at any length.  Precision: the
step ``Δ`` (softplus), the decays, their running sums and exponentials and
the carried state are float32 whatever the operands' dtype; the matrix
products take their operands in the compute dtype (``x``'s: bf16 under AMP)
with float32 accumulation.  The ops are in none of ``amp/lists.py``'s
tiers: a cast of every input, up or down, would be wrong for one half of
them.  Differentiable by autodiff through the chunked form (no S × S and no
per-position state is ever kept: the residuals are the chunk's Q × Q blocks
and the S / chunk states).  A length that no chunk divides is padded inside
the op: zeros after the end change nothing before it.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .registry import register

__all__ = ["causal_conv1d", "ssm_scan", "gated_rms_norm", "mamba2_mixer"]

_F32 = jnp.float32


def _prec(dtype):
    # named, not None: the package's global default is 'highest'
    return (jax.lax.Precision.HIGHEST if dtype == _F32
            else jax.lax.Precision.DEFAULT)


@register("causal_conv1d")
def causal_conv1d(data, weight, bias=None, activation=None):
    """Depthwise causal convolution over time: ``data`` [B, S, C], ``weight``
    [C, K], ``bias`` [C]; ``y_t = Σ_k w[:, k] · x_{t−(K−1)+k} (+ b)``, with
    zeros before the start — tap ``K−1`` multiplies the position itself.
    K shifted multiply-adds in float32 (K is 4: no work for the MXU), stored
    in the input's dtype; ``activation`` by name (``"silu"``)."""
    s, taps = data.shape[1], weight.shape[1]
    x = jnp.pad(data.astype(_F32), ((0, 0), (taps - 1, 0), (0, 0)))
    w = weight.astype(_F32)
    out = sum(x[:, k:k + s, :] * w[:, k] for k in range(taps))
    if bias is not None:
        out = out + bias.astype(_F32)
    if activation:
        from .nn import _ACTS

        out = _ACTS[activation](out)
    return out.astype(data.dtype)


@register("gated_rms_norm")
def gated_rms_norm(data, gate, gamma, num_groups=1, eps=1e-5):
    """``RMSNorm(data · silu(gate))`` with the statistics over each of
    ``num_groups`` equal groups of the last axis and one gain an element
    (Mamba-2's gated norm, ``norm_before_gate`` false); float32 inside,
    stored in the input's dtype."""
    x = data.astype(_F32) * jax.nn.silu(gate.astype(_F32))
    grouped = x.reshape(x.shape[:-1] + (int(num_groups), -1))
    ms = jnp.mean(jnp.square(grouped), axis=-1, keepdims=True)
    out = (grouped * jax.lax.rsqrt(ms + eps)).reshape(x.shape)
    return (out * gamma.astype(_F32)).astype(data.dtype)


def _count_scan():
    """One count a traced call site, as ``attention_dispatch_*`` are."""
    from .. import profiler

    profiler.incr("ssm_scan_traced")


@register("ssm_scan")
def ssm_scan(x, dt, a_log, b, c, d_skip, dt_bias, chunk_size=128,
             dt_floor=0.0):
    """The chunked state-space scan.  ``x`` [B, S, H, P]; ``dt`` [B, S, H]
    (the raw step: ``Δ = max(softplus(dt + dt_bias), dt_floor)``); ``a_log``,
    ``d_skip``, ``dt_bias`` [H] (``A = −exp(a_log)``); ``b``, ``c``
    [B, S, G, N], head ``h`` reading group ``h // (H / G)``.  Returns ``y``
    [B, S, H, P] in ``x``'s dtype."""
    _count_scan()
    bsz, s, h, p = x.shape
    g, n = b.shape[2], b.shape[3]
    if h % g:
        raise ValueError(f"{h} heads do not divide into {g} groups")
    r = h // g                                                 # heads a group
    q = min(int(chunk_size), s)
    cdt, prec = x.dtype, _prec(x.dtype)
    mm = lambda spec, *ops: jnp.einsum(spec, *ops, precision=prec,
                                       preferred_element_type=_F32)

    delta = jax.nn.softplus(dt.astype(_F32) + dt_bias.astype(_F32))
    delta = jnp.maximum(delta, dt_floor)                       # [B, S, H]
    pad = -s % q
    if pad:
        # Δ = 0 after the end: a decay of 1 and an update of 0
        grow = lambda a: jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
        x, delta, b, c = grow(x), grow(delta), grow(b), grow(c)
    nc = (s + pad) // q
    # chunked and head-major: a head's chunk is a [Q, ·] tile, and what is one
    # number a head and a position (Δ, log decay, running sum) is a row of Q
    xh = x.reshape(bsz, nc, q, h, p).transpose(0, 1, 3, 2, 4)  # [B, nc, H, Q, P]
    dl = delta.reshape(bsz, nc, q, h).transpose(0, 1, 3, 2)    # [B, nc, H, Q]
    bc = b.reshape(bsz, nc, q, g, n).astype(cdt)
    cc = c.reshape(bsz, nc, q, g, n).astype(cdt)
    cum = jnp.cumsum(dl * -jnp.exp(a_log.astype(_F32))[:, None], axis=-1)  # of log decay ≤ 0
    last = cum[..., -1]                                        # [B, nc, H]
    grouped = lambda a: a.reshape(a.shape[:2] + (g, r) + a.shape[3:])
    headed = lambda a: a.reshape(a.shape[:2] + (h,) + a.shape[4:])

    # inside a chunk: ((C Bᵀ) ∘ L ∘ Δ_j) x̃
    cb = mm("bzign,bzjgn->bzgij", cc, bc)                      # [B, nc, G, Q, Q]
    lower = jnp.tril(jnp.ones((q, q), bool))
    # masked BEFORE the exponential: above the diagonal the difference is ≥ 0
    decay = jnp.exp(jnp.where(lower, cum[..., :, None] - cum[..., None, :], -jnp.inf))
    weight = headed(cb[:, :, :, None] * grouped(decay * dl[..., None, :]))
    y = mm("bzhij,bzhjp->bzhip", weight.astype(cdt), xh)       # [B, nc, H, Q, P]

    # the chunk's own state, and the states carried from chunk to chunk
    to_end = jnp.exp(last[..., None] - cum) * dl               # [B, nc, H, Q]
    xs = (xh.astype(_F32) * to_end[..., None]).astype(cdt)
    states = headed(mm("bzgrjp,bzjgn->bzgrpn", grouped(xs), bc))  # [B, nc, H, P, N]

    def carry(state, chunk):
        own, keep = chunk
        return keep[..., None, None] * state + own, state      # emits the state ENTERING

    _, entering = jax.lax.scan(
        carry, jnp.zeros((bsz, h, p, n), _F32),
        (states.swapaxes(0, 1), jnp.exp(last).swapaxes(0, 1)))
    entering = entering.swapaxes(0, 1)                         # [B, nc, H, P, N]

    # what the entering state gives position i: exp(cum_i) · C_i h
    off = headed(mm("bzign,bzgrpn->bzgrip", cc, grouped(entering.astype(cdt))))
    y = y + off * jnp.exp(cum)[..., None]
    y = y + xh.astype(_F32) * d_skip.astype(_F32)[:, None, None]
    y = y.astype(cdt).transpose(0, 1, 3, 2, 4)                 # [B, nc, Q, H, P]
    return y.reshape(bsz, nc * q, h, p)[:, :s]


@register("mamba2_mixer")
def mamba2_mixer(x, w_in, conv_w, conv_b, dt_bias, a_log, d_skip, gamma,
                 w_out, num_heads=1, head_dim=64, n_groups=1, state_size=128,
                 chunk_size=128, dt_floor=0.0, eps=1e-5, scope="mamba"):
    """One Mamba-2 mixer on ``x`` [B, S, d] (already normed): ``[z | xBC |
    dt] = x W_in``; ``xBC = silu(conv(xBC) + b)``; split ``x̃ | B | C``; the
    scan; ``RMSNorm_grouped(y · silu(z))``; ``W_out``.  Weights are ``[out,
    in]``, no projection bias.  ``scope`` names the ``jax.named_scope`` of the
    whole op and, beneath it, ``.in_proj``, ``.conv``, ``.scan``,
    ``.gate_norm`` and ``.out_proj``."""
    bsz, s, _ = x.shape
    h, p, g, n = int(num_heads), int(head_dim), int(n_groups), int(state_size)
    inner, bc = h * p, g * n
    prec = _prec(x.dtype)

    def proj(a, w):
        return jnp.einsum("...i,oi->...o", a, w.astype(a.dtype), precision=prec)

    with jax.named_scope(scope):
        with jax.named_scope(scope + ".in_proj"):
            zxbcdt = proj(x, w_in)
            z = zxbcdt[..., :inner]
            xbc = zxbcdt[..., inner:2 * inner + 2 * bc]
            dt = zxbcdt[..., 2 * inner + 2 * bc:]
        with jax.named_scope(scope + ".conv"):
            xbc = causal_conv1d(xbc, conv_w, conv_b, activation="silu")
        with jax.named_scope(scope + ".scan"):
            y = ssm_scan(
                xbc[..., :inner].reshape(bsz, s, h, p), dt, a_log,
                xbc[..., inner:inner + bc].reshape(bsz, s, g, n),
                xbc[..., inner + bc:].reshape(bsz, s, g, n),
                d_skip, dt_bias, chunk_size=chunk_size, dt_floor=dt_floor)
        with jax.named_scope(scope + ".gate_norm"):
            y = gated_rms_norm(y.reshape(bsz, s, inner), z, gamma,
                               num_groups=g, eps=eps)
        with jax.named_scope(scope + ".out_proj"):
            return proj(y, w_out)
