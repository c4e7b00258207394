"""State-space layers (Mamba-2): the causal depthwise convolution over time,
the chunked state-space scan and the grouped gated RMSNorm, and the mixer
that strings them together (``gluon.model_zoo.nemotron_h`` takes it;
docs/nemotron_h.md has the equations).  LFM2's gated short convolution
(:func:`gated_short_conv`, ``gluon.model_zoo.lfm2_moe``; docs/lfm2.md) is
the same convolution between two elementwise gates.

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
them.  On a TPU the chunks run in two Pallas kernels, forward and backward
(:func:`_scan_path` says when); elsewhere in XLA, differentiable by autodiff
through the chunked form.  No S × S and no per-position state is ever kept:
the residuals are the S / chunk states (and, in XLA, the chunk's Q × Q
blocks).  A length that no chunk divides is padded inside the op: zeros after
the end change nothing before it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as _pl
from jax.experimental.pallas import tpu as _pltpu

from . import attention as _att
from .registry import register

__all__ = ["causal_conv1d", "gated_short_conv", "ssm_scan", "gated_rms_norm", "mamba2_mixer"]

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


@register("gated_short_conv")
def gated_short_conv(bcx, weight):
    """LFM2's gated short convolution on the in-projection's output ``bcx``
    [B, S, 3·C], chunked ``[B | C | x̃]``: ``C ⊙ conv(B ⊙ x̃)``, the
    convolution :func:`causal_conv1d`'s with ``weight`` [C, K], no bias and
    no activation.  Float32 inside, stored in ``bcx``'s dtype.  One count of
    ``short_conv_traced`` a traced call site."""
    from .. import profiler

    profiler.incr("short_conv_traced")
    b, c, x = jnp.split(bcx.astype(_F32), 3, axis=-1)
    return (c * causal_conv1d(b * x, weight)).astype(bcx.dtype)


@register("gated_rms_norm")
def gated_rms_norm(data, gate, gamma, num_groups=1, eps=1e-5, form="mamba"):
    """A gated RMSNorm with the statistics over each of ``num_groups`` equal
    groups of the last axis and one gain an element; float32 inside, stored
    in the input's dtype.  ``form`` ``"mamba"`` is Mamba-2's
    ``RMSNorm(data · silu(gate))``, ``"kda"`` KDA's output norm
    ``RMSNorm(data) · σ(gate)``."""
    if form not in ("mamba", "kda"):
        raise ValueError(f"gated_rms_norm form {form!r}: 'mamba' or 'kda'")
    x = data.astype(_F32)
    if form == "mamba":
        x = x * jax.nn.silu(gate.astype(_F32))
    grouped = x.reshape(x.shape[:-1] + (int(num_groups), -1))
    ms = jnp.mean(jnp.square(grouped), axis=-1, keepdims=True)
    out = (grouped * jax.lax.rsqrt(ms + eps)).reshape(x.shape) * gamma.astype(_F32)
    if form == "kda":
        out = out * jax.nn.sigmoid(gate.astype(_F32))
    return out.astype(data.dtype)


def _count_scan():
    """One count a traced call site, as ``attention_dispatch_*`` are."""
    from .. import profiler

    profiler.incr("ssm_scan_traced")


# The same chunks as two Pallas kernels, forward and backward.  A cell of the
# grid (batch row, group, chunk) reads the chunk's C and B ([Q, N]), the x̃
# columns of the group's r heads ([Q, r·P]) and their Δ and running sums of log
# decay (one row of Q a head) straight from the operands' own layout, forms
# C Bᵀ ONCE for the r heads, and for each head the masked decay block, the
# weights, y and the head's [P, N] float32 state, which it carries to the next
# chunk in VMEM scratch: the chunk axis is the innermost of the grid and runs in
# order (in reverse in the backward kernel, which carries dh the same way).  The
# backward reads each chunk's entering state from the forward rule's residual
# ([B, nc, H, P, N] float32: alive inside a layer's backward only, under the
# layer checkpoint).  Every rounding point is the XLA form's: Δ, the running
# sums, decays, exponentials and the state float32; the products on operands in
# the compute dtype with float32 accumulation, the weight block, x̃ · to_end and
# the entering state cast to it before their products.

_SCAN_VMEM_LIMIT = 32 << 20
_NT, _NN, _TN = _att._NT, _att._NN, _att._TN


def _turned(v, axis):
    """A [1, Q] row as a [Q, 1] column (``axis`` 1) or a column as a row
    (``axis`` 0), exactly: a select against the identity and a sum with
    zeros."""
    q = max(v.shape)
    eye = (jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
           == jax.lax.broadcasted_iota(jnp.int32, (q, q), 1))
    return jnp.sum(jnp.where(eye, jnp.broadcast_to(v, (q, q)), 0.0), axis=axis, keepdims=True)


_col = functools.partial(_turned, axis=1)
_row = functools.partial(_turned, axis=0)


def _lanes(v, like):
    """A [1, 1] value along the lanes of ``like``'s rows, [1, lanes]: Mosaic
    broadcasts in sublanes or in lanes, not in both at once (and folds two
    broadcasts in a row into one; a sum with zeros keeps them apart)."""
    return v + jnp.zeros((1, like.shape[1]), _F32)


def _chunk_terms(dl_ref, cum_ref, k, lower):
    """Head ``k``'s float32 terms of a chunk: Δ and running sum as rows, the
    running sum as a column, the chunk's last running sum [1, 1] and the
    masked decay block ``exp(cum_i − cum_j)``, i ≥ j (masked BEFORE the
    exponential: above the diagonal the difference is ≥ 0)."""
    dl = dl_ref[0, 0, k:k + 1, :]
    cum = cum_ref[0, 0, k:k + 1, :]
    cum_col = _col(cum)
    q = cum.shape[1]
    decay = jnp.exp(jnp.where(lower, cum_col - cum, -jnp.inf))
    return dl, cum, cum_col, cum[:, q - 1:q], decay


def _scan_fwd_kernel(x_ref, dl_ref, cum_ref, b_ref, c_ref, d_ref, y_ref, *rest, width):
    """x_ref / y_ref [1, Q, r·P], dl_ref / cum_ref [1, 1, r, Q] float32,
    b_ref / c_ref [1, Q, N], d_ref [1, r, 128] float32 (D a head, along the
    lanes); ``rest`` is (states_ref [1, 1, r, P, N] float32, h_ref) for the
    forward rule, which writes each chunk's ENTERING state, or (h_ref,):
    the [r, P, N] float32 states carried from chunk to chunk."""
    states_ref, h_ref = (None,) + rest if len(rest) == 1 else rest
    heads = h_ref.shape[0]
    cdt = x_ref.dtype
    mm = functools.partial(jax.lax.dot_general, precision=_prec(cdt),
                           preferred_element_type=_F32)

    @_pl.when(_pl.program_id(2) == 0)
    def _():
        h_ref[...] = jnp.zeros(h_ref.shape, _F32)

    bm, cm = b_ref[0], c_ref[0]
    q = bm.shape[0]
    lower = (jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
             >= jax.lax.broadcasted_iota(jnp.int32, (q, q), 1))
    cb = mm(cm, bm, _NT)                                       # C Bᵀ, once a group
    for k in range(heads):
        cols = slice(k * width, (k + 1) * width)
        xk = x_ref[0, :, cols]
        dl, cum, cum_col, last, decay = _chunk_terms(dl_ref, cum_ref, k, lower)
        y = mm((cb * (decay * dl)).astype(cdt), xk, _NN)
        h = h_ref[k]                                           # entering
        if states_ref is not None:
            states_ref[0, 0, k] = h
        y = y + mm(cm, h.astype(cdt), _NT) * jnp.exp(cum_col)
        y = y + xk.astype(_F32) * d_ref[0, k:k + 1, 0:width]
        y_ref[0, :, cols] = y.astype(y_ref.dtype)
        to_end = _col(jnp.exp(last - cum) * dl)
        xs = (xk.astype(_F32) * to_end).astype(cdt)
        h_ref[k] = _lanes(jnp.exp(last), h) * h + mm(xs, bm, _TN)


def _scan_bwd_kernel(x_ref, dl_ref, cum_ref, b_ref, c_ref, d_ref, states_ref, dy_ref,
                     dx_ref, ddl_ref, dcum_ref, db_ref, dc_ref, dd_ref, dh_ref, *, width):
    """The forward's operands, its entering states and dy_ref [1, Q, r·P]
    → dx, dΔ, d(running sum), dB and dC (the group's heads summed in VMEM),
    and dd_ref [1, r, 128] float32 (each head's Σ dy · x̃ over the chunks in
    lane 0, zeros in the others: the transpose of D's broadcast along the
    lanes sums them).  The chunks run in reverse; dh_ref [r, P, N]
    float32 carries the gradient of the state leaving each chunk."""
    heads = dh_ref.shape[0]
    cdt = x_ref.dtype
    mm = functools.partial(jax.lax.dot_general, precision=_prec(cdt),
                           preferred_element_type=_F32)

    @_pl.when(_pl.program_id(2) == 0)
    def _():
        dh_ref[...] = jnp.zeros(dh_ref.shape, _F32)
        dd_ref[...] = jnp.zeros(dd_ref.shape, _F32)

    bm, cm = b_ref[0], c_ref[0]
    q = bm.shape[0]
    lower = (jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
             >= jax.lax.broadcasted_iota(jnp.int32, (q, q), 1))
    is_last = jax.lax.broadcasted_iota(jnp.int32, (1, q), 1) == q - 1
    first_lane = jax.lax.broadcasted_iota(jnp.int32, (1, dd_ref.shape[2]), 1) == 0
    cb = mm(cm, bm, _NT)
    dcb = jnp.zeros((q, q), _F32)
    db = jnp.zeros(bm.shape, _F32)
    dc = jnp.zeros(cm.shape, _F32)
    for k in range(heads):
        cols = slice(k * width, (k + 1) * width)
        xk = x_ref[0, :, cols]
        xf = xk.astype(_F32)
        dy = dy_ref[0, :, cols].astype(_F32)
        dyc = dy.astype(cdt)
        dl, cum, cum_col, last, decay = _chunk_terms(dl_ref, cum_ref, k, lower)
        h, dh_out = states_ref[0, 0, k], dh_ref[k]
        hb = h.astype(cdt)
        dd_ref[0, k:k + 1, :] += jnp.where(first_lane, jnp.sum(dy * xf), 0.0)
        # y = y_diag + exp(cum_i) · C_i h + D · x̃
        e_col = jnp.exp(cum_col)
        dx = dy * d_ref[0, k:k + 1, 0:width]
        doff = (dy * e_col).astype(cdt)
        dcum_col = jnp.sum(dy * mm(cm, hb, _NT), axis=1, keepdims=True) * e_col
        dc = dc + mm(doff, hb, _NN)
        dh_in = _lanes(jnp.exp(last), dh_out) * dh_out + mm(doff, cm, _TN)
        # y_diag = W x̃, W = (C Bᵀ) ∘ L ∘ Δ_j
        dec_dl = decay * dl
        wf = cb * dec_dl
        dw = mm(dyc, xk, _NT)
        dx = dx + mm(wf.astype(cdt), dyc, _TN)
        dcb = dcb + dw * dec_dl
        g = dw * wf
        ddl = jnp.sum(dw * cb * decay, axis=0, keepdims=True)
        dcum_col = dcum_col + jnp.sum(g, axis=1, keepdims=True)
        dcum = -jnp.sum(g, axis=0, keepdims=True)
        # h_out = exp(last) h + xsᵀ B, xs = x̃ · exp(last − cum_j) Δ_j
        to_last = jnp.exp(last - cum)
        t_col = _col(to_last * dl)
        ds = dh_out.astype(cdt)
        db = db + mm((xf * t_col).astype(cdt), ds, _NN)
        dxs = mm(bm, ds, _NT)
        dx = dx + dxs * t_col
        dt_col = jnp.sum(dxs * xf, axis=1, keepdims=True)
        ddl = ddl + _row(dt_col) * to_last
        dt_t = _row(dt_col * t_col)
        dlast = jnp.sum(dh_out * h) * jnp.exp(last) + jnp.sum(dt_t)
        dcum = dcum + _row(dcum_col) - dt_t + jnp.where(is_last, dlast, 0.0)
        ddl_ref[0, 0, k:k + 1, :] = ddl
        dcum_ref[0, 0, k:k + 1, :] = dcum
        dx_ref[0, :, cols] = dx.astype(dx_ref.dtype)
        dh_ref[k] = dh_in
    # C Bᵀ, shared by the group's heads
    dcb = dcb.astype(cdt)
    db_ref[0] = (db + mm(dcb, cm, _TN)).astype(db_ref.dtype)
    dc_ref[0] = (dc + mm(dcb, bm, _NN)).astype(dc_ref.dtype)


def _scan_launch(x, dl, b, groups, reverse):
    """What both kernels' launches share: the grid (batch row, group, chunk),
    the operands' block specs (x̃ or dy, Δ, running sum, B, C, D, entering
    state), the widths and the compiler's parameters; ``reverse`` runs the
    chunks last to first."""
    bsz, s, hp = x.shape
    nc, h, q = dl.shape[1:]
    r, n = h // groups, b.shape[2] // groups
    p = hp // h
    at = (lambda z: nc - 1 - z) if reverse else (lambda z: z)
    rows = _pl.BlockSpec((1, q, r * p), lambda i, j, z: (i, at(z), j))
    heads = _pl.BlockSpec((1, 1, r, q), lambda i, j, z: (i, at(z), j, 0))
    state = _pl.BlockSpec((1, q, n), lambda i, j, z: (i, at(z), j))
    specs = {"rows": rows, "heads": heads, "bc": state,
             "d": _pl.BlockSpec((1, r, 128), lambda i, j, z: (i, j, 0)),
             "states": _pl.BlockSpec((1, 1, r, p, n), lambda i, j, z: (i, at(z), j, 0, 0))}
    params = _pltpu.CompilerParams(dimension_semantics=("parallel", "parallel", "arbitrary"),
                                   vmem_limit_bytes=_SCAN_VMEM_LIMIT)
    return (bsz, groups, nc), specs, (r, p, n), params


@functools.partial(jax.jit, static_argnames=("groups", "with_states", "interpret"))
def _scan_fwd_pallas(x, dl, cum, b, c, d, *, groups, with_states, interpret=False):
    """x [B, S, H·P]; dl, cum [B, nc, H, Q] float32; b, c [B, S, G·N]; d [B,
    H, 128] float32 → y [B, S, H·P] in x's dtype, and with ``with_states``
    each chunk's entering state [B, nc, H, P, N] float32."""
    grid, sp, (r, p, n), params = _scan_launch(x, dl, b, groups, False)
    nc, h = dl.shape[1:3]
    out_shape = [jax.ShapeDtypeStruct(x.shape, x.dtype)]
    out_specs = [sp["rows"]]
    if with_states:
        out_shape.append(jax.ShapeDtypeStruct((x.shape[0], nc, h, p, n), _F32))
        out_specs.append(sp["states"])
    out = _pl.pallas_call(
        functools.partial(_scan_fwd_kernel, width=p),
        out_shape=tuple(out_shape),
        grid=grid,
        in_specs=[sp["rows"], sp["heads"], sp["heads"], sp["bc"], sp["bc"], sp["d"]],
        out_specs=tuple(out_specs),
        scratch_shapes=[_pltpu.VMEM((r, p, n), _F32)],
        interpret=interpret,
        name="ssm_scan_fwd",
        compiler_params=params,
    )(x, dl, cum, b, c, d)
    return out if with_states else out[0]


@functools.partial(jax.jit, static_argnames=("groups", "interpret"))
def _scan_bwd_pallas(x, dl, cum, b, c, d, states, dy, *, groups, interpret=False):
    """The forward's operands, its entering states and dy → (dx, dΔ,
    d(running sum), dB, dC, dD's [B, H, 128] float32 partial sums in lane 0)."""
    grid, sp, (r, p, n), params = _scan_launch(x, dl, b, groups, True)
    dx, ddl, dcum, db, dc, dd = _pl.pallas_call(
        functools.partial(_scan_bwd_kernel, width=p),
        out_shape=(jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct(dl.shape, _F32),
                   jax.ShapeDtypeStruct(dl.shape, _F32),
                   jax.ShapeDtypeStruct(b.shape, b.dtype),
                   jax.ShapeDtypeStruct(c.shape, c.dtype),
                   jax.ShapeDtypeStruct(d.shape, _F32)),
        grid=grid,
        in_specs=[sp["rows"], sp["heads"], sp["heads"], sp["bc"], sp["bc"], sp["d"],
                  sp["states"], sp["rows"]],
        out_specs=(sp["rows"], sp["heads"], sp["heads"], sp["bc"], sp["bc"], sp["d"]),
        scratch_shapes=[_pltpu.VMEM((r, p, n), _F32)],
        interpret=interpret,
        name="ssm_scan_bwd",
        compiler_params=params,
    )(x, dl, cum, b, c, d, states, dy)
    return dx, ddl, dcum, db, dc, dd


def _launched(entry, launch, groups, *arrays, **static):
    """``entry`` (one of the two above) where and how ``launch`` says."""
    return _att._on_mesh(launch, functools.partial(entry, groups=groups, interpret=launch.interpret,
                                              **static), *arrays)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _scan_kernels(x, dl, cum, b, c, d, groups, launch):
    """The chunked scan in the Pallas kernels, forward and backward: x [B, S,
    H·P], dl / cum [B, nc, H, Q] float32 (Δ and the running sum of log decay
    inside each chunk, head-major), b / c [B, S, G·N] in x's dtype, d [B, H,
    128] float32 → y [B, S, H·P]."""
    return _launched(_scan_fwd_pallas, launch, groups, x, dl, cum, b, c, d, with_states=False)


def _scan_kernels_fwd(x, dl, cum, b, c, d, groups, launch):
    y, states = _launched(_scan_fwd_pallas, launch, groups, x, dl, cum, b, c, d,
                          with_states=True)
    return y, (x, dl, cum, b, c, d, states)


def _scan_kernels_bwd(groups, launch, res, dy):
    return _launched(_scan_bwd_pallas, launch, groups, *res, dy)


_scan_kernels.defvjp(_scan_kernels_fwd, _scan_kernels_bwd)


def _scan_path(x, b, q):
    """THE predicate of :func:`ssm_scan`: the kernels' launch, or None for the
    XLA chunks.  Decided once a call from what the (padded) operands and the
    trace show, as ``attention._kernel_path`` and
    ``sparse_attention._index_path`` decide theirs: the platform (the
    interpreter only by name), a chunk that divides the padded length, and
    for the compiled kernels bfloat16 operands, a chunk Q, a group's r·P
    columns and a state N that fill whole 128-lane columns, and VMEM for the
    blocks; and the mesh the trace is for (no compiler partitions a Mosaic
    kernel: a batch split over ``dp`` / ``fsdp`` launches under a
    ``shard_map``, any other split takes the XLA chunks)."""
    bsz, s, h, p = x.shape
    g, n = b.shape[2:]
    rp = h // g * p
    use, interpret = _att._use_pallas(x)
    if not use or s % q:
        return None
    if not interpret:
        isz = jnp.dtype(x.dtype).itemsize
        # the backward's blocks, double-buffered: x̃, dy, dx [Q, r·P]; B, C,
        # dB, dC [Q, N]; the entering states [r, P, N] float32; the carried dh;
        # ~16 [Q, Q] float32 values; 8 MiB of the limit left to the compiler
        vmem = (2 * (3 * q * rp * isz + 4 * q * n * isz + rp * n * 4)
                + rp * n * 4 + 16 * q * q * 4)
        if (x.dtype != jnp.bfloat16 or q % 128 or rp % 128 or n % 128
                or vmem > _SCAN_VMEM_LIMIT - (8 << 20)):
            return None
    where = _att._rows_split(bsz)
    return None if where is None else _att._Launch(interpret, None, *where)


@register("ssm_scan")
def ssm_scan(x, dt, a_log, b, c, d_skip, dt_bias, chunk_size=128,
             dt_floor=0.0):
    """The chunked state-space scan.  ``x`` [B, S, H, P]; ``dt`` [B, S, H]
    (the raw step: ``Δ = max(softplus(dt + dt_bias), dt_floor)``); ``a_log``,
    ``d_skip``, ``dt_bias`` [H] (``A = −exp(a_log)``); ``b``, ``c``
    [B, S, G, N], head ``h`` reading group ``h // (H / G)``.  Returns ``y``
    [B, S, H, P] in ``x``'s dtype.

    One algorithm, two implementations, chosen a call by :func:`_scan_path`:
    on a TPU two Pallas kernels (forward; one backward) that carry a group's
    chunk state in VMEM, everywhere else XLA (the chunks' products as
    einsums, the states carried by a ``lax.scan``, autodiff through both).
    The counters ``ssm_scan_dispatch_pallas`` / ``ssm_scan_dispatch_xla``
    say which way a call site was traced."""
    from .. import profiler

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
    launch = _scan_path(x, b, q)
    profiler.incr("ssm_scan_dispatch_xla" if launch is None else "ssm_scan_dispatch_pallas")
    # chunked and head-major: a head's chunk is a [Q, ·] tile, and what is one
    # number a head and a position (Δ, log decay, running sum) is a row of Q
    dl = delta.reshape(bsz, nc, q, h).transpose(0, 1, 3, 2)    # [B, nc, H, Q]
    cum = jnp.cumsum(dl * -jnp.exp(a_log.astype(_F32))[:, None], axis=-1)  # of log decay ≤ 0
    if launch is not None:
        lanes = lambda a: a.reshape(bsz, nc * q, -1).astype(cdt)
        d = jnp.broadcast_to(d_skip.astype(_F32)[None, :, None], (bsz, h, 128))
        y = _scan_kernels(lanes(x), dl, cum, lanes(b), lanes(c), d, g, launch)
        return y.reshape(bsz, nc * q, h, p)[:, :s]
    xh = x.reshape(bsz, nc, q, h, p).transpose(0, 1, 3, 2, 4)  # [B, nc, H, Q, P]
    bc = b.reshape(bsz, nc, q, g, n).astype(cdt)
    cc = c.reshape(bsz, nc, q, g, n).astype(cdt)
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
