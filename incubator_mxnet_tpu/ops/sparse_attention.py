"""Attention over the keys a learned indexer selects for each query
(DeepSeek-V3.2's sparse attention; docs/keye.md has the equations).

Every other attention call of the package attends to every visible key.
Here a small **lightning indexer** scores each (query, key) pair, each
query keeps its ``topk`` best visible keys, and the main attention's softmax
runs over those alone.  Five pieces, each usable alone, and
:func:`sparse_attention`, the registered layer that ``gluon.model_zoo.keye``
calls:

* :func:`index_scores` — ``I[t, s] = Σ_j H^-½ w[t, j] · relu(q_I[t, j] · k_I[s]
  · D^-½)`` in float32 over ``H`` index heads that share ONE index key, worked
  in ``q_chunk × kv_chunk`` tiles (tiles past the diagonal are skipped) so that
  the ``[H, S, S]`` products never exist whole.  One algorithm, two
  implementations, chosen a call by :func:`_index_path` from the platform,
  the shapes and the mesh: on a TPU two Pallas kernels (forward; one backward
  for ``dq_I``, ``dk_I`` and ``dw``) whose grid cell keeps a query block's
  heads, a key block and the tile in VMEM and loops over the heads inside;
  everywhere else XLA tiles (a scan of a scan).  Float32 means six bfloat16
  partial products summed in float32 (``Precision.HIGHEST``) on both; the
  kernels write them out and stack two to an MXU pass.
* :func:`select_topk` — each query's ``topk`` highest-scored visible keys (all
  of them while it sees no more than ``topk``), as an int8 ``[B, S, S]``
  selection: exactly ``min(t + 1, topk)`` keys a row, ties to the lower index.
  The ``k``-th largest score of a row is found by bisection on the scores'
  bits (32 counting passes over the row, no sort).
* the main attention under that selection: the attention dispatcher
  (``ops.attention._attend_bshd(..., select=)``; the blockwise kernels read the
  selection as a mask operand shared by the heads).
* :func:`head_mean_probs` — the main attention's probabilities averaged over
  its heads, ``[B, S, S]`` float32: the indexer's target.  On the kernels' path
  a Pallas kernel recomputes each head's scores from q, k and the forward's
  log-sum-exp tile by tile and sums them in VMEM; no ``[heads, S, S]`` array
  exists.
* :func:`indexer_kl_loss` — ``mean_t KL(p[t, ·] ‖ softmax_{s ∈ S_t} I[t, s])``,
  the indexer's own loss.

Who gets which gradient: the indexer reads ``stop_gradient(x)`` and its target
is detached, so its loss moves the indexer's parameters only; the selection is
integer-valued and carries none, so the language-model loss moves everything
else and never the indexer.

In ``amp/lists.py``: :func:`sparse_attention` is a TARGET op (its projections
ride the MXU in the low-precision dtype); index scores, the selection's
comparisons, soft-maxes and the loss are float32 inside it whatever arrives,
and the pieces registered alone (:func:`index_scores`,
:func:`indexer_kl_loss`) are FP32 ops.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as _pl
from jax.experimental.pallas import tpu as _pltpu

from . import attention as _att
from .registry import register

__all__ = ["index_scores", "select_topk", "head_mean_probs", "indexer_kl_loss",
           "live_tiles", "sparse_attention"]

_F32 = jnp.float32
_HIGHEST = lax.Precision.HIGHEST

# What :func:`select_topk` calls the selection it found by bisection
# (``checkpoint_name``), for a checkpoint whose policy saves the name
KEEP_SELECT = "attn.select"
# ... while a query's row of it is no longer than this many times ``topk``
_KEEP_SELECT_MAX_ROW = 4


def _pad_rows(x, axis, multiple):
    pad = -x.shape[axis] % multiple
    if not pad:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _chunks(x, axis, size):
    """``x`` with ``axis`` split into leading chunks: [n, ..., size, ...]."""
    n = x.shape[axis] // size
    shape = x.shape[:axis] + (n, size) + x.shape[axis + 1:]
    return jnp.moveaxis(x.reshape(shape), axis, 0)


# ---------------------------------------------------------------------------
# The lightning indexer's scores
# ---------------------------------------------------------------------------


def _score_tile(q, k, w, q0, k0):
    """One tile: q [B, Cq, H, D], k [B, Ck, D], w [B, Cq, H] float32 →
    [B, Cq, Ck] float32, ``-inf`` where the key lies past the query."""
    heads, dim = q.shape[2], q.shape[3]
    prod = jnp.einsum("bqhd,bkd->bqhk", q, k, precision=_HIGHEST,
                      preferred_element_type=_F32) * dim ** -0.5
    tile = jnp.sum(jax.nn.relu(prod) * w[..., None], axis=2) * heads ** -0.5
    t = q0 + lax.broadcasted_iota(jnp.int32, tile.shape, 1)
    s = k0 + lax.broadcasted_iota(jnp.int32, tile.shape, 2)
    return jnp.where(t >= s, tile, -jnp.inf)


def _index_scores_tiles(q_idx, k_idx, w, cq, ck):
    """:func:`index_scores` as XLA tiles: a scan over query chunks of a scan
    over key chunks, a tile wholly past the diagonal not computed, each
    tile's products computed again in the backward pass; a length no chunk
    divides is padded inside."""
    b, s = q_idx.shape[:2]
    q, w, k = (_pad_rows(a, 1, c) for a, c in ((q_idx, cq), (w, cq), (k_idx, ck)))
    k_chunks = _chunks(k, 1, ck)                               # [nk, B, Ck, D]
    tile = jax.checkpoint(_score_tile)

    def rows(i, qw):
        qc, wc = qw

        def cols(j, kc):
            return j + 1, lax.cond(
                j * ck <= i * cq + cq - 1,
                lambda: tile(qc, kc, wc, i * cq, j * ck),
                lambda: jnp.full((b, cq, ck), -jnp.inf, _F32))

        _, tiles = lax.scan(cols, 0, k_chunks)                 # [nk, B, Cq, Ck]
        return i + 1, jnp.moveaxis(tiles, 0, 2).reshape(b, cq, -1)

    _, out = lax.scan(rows, 0, (_chunks(q, 1, cq), _chunks(w, 1, cq)))
    return jnp.moveaxis(out, 0, 1).reshape(b, q.shape[1], -1)[:, :s, :s]


# The same tiles as two Pallas kernels, forward and backward.  A cell of the
# grid (batch row, query block, key block) holds the query block's H heads, one
# key block and the [Bk, Bq] tile in VMEM: the [H, Bq, Bk] per-head products
# never reach HBM.  The tile is held TRANSPOSED (keys down, queries across, as
# the blockwise attention backward and ``_head_probs_kernel`` hold theirs), so
# that a head's weights are a row that broadcasts down the tile and ``dw`` is a
# sum down it; it is turned once a tile, on its way out (or, ``dI``, in).
#
# Precision: float32 products at ``Precision.HIGHEST`` are six bfloat16
# products with float32 sums — x = hi + mid + lo, each a bfloat16, and of the
# nine partial products the six that matter: hi·hi, hi·mid, mid·hi, mid·mid,
# hi·lo, lo·hi.  The kernels form the SAME six, written out, and stack two of
# them along a dimension the MXU would otherwise leave half empty (its tiles
# are 128 x 128, an index head 64 wide): scores contract [q_hi | q_mid] with
# [k_hi | k_mid] (hi·hi + mid·mid in one pass, summed in the MXU's float32
# accumulator), with [k_mid | k_hi] (the two hi·mid), and [q_hi | q_lo] with
# [k_lo | k_hi] (the two hi·lo): three passes at a contraction of 2 D where
# six at D would run.  The gradients' products contract over a block's 512
# rows and stack along their 2 D output columns: g_hi and g_mid against
# [x_hi | x_mid], g_hi against the lo stack, g_lo against [x_hi | x_mid],
# the two halves that are not among the six discarded: four passes where six
# half-wide ones would run.  The parts of q and k are split off outside the
# kernels (elementwise XLA, ``lax.reduce_precision``, which no simplification
# removes), the parts of ``g`` inside.

_BF16 = jnp.bfloat16
_INDEX_VMEM_LIMIT = 100 << 20     # of a v5e core's 128 MiB


def _bf16_parts(x):
    """float32 → (hi, mid, lo): float32 arrays that each hold bfloat16
    values exactly, hi + mid + lo == x to x's 24 bits."""
    as_bf16 = lambda a: lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)
    hi = as_bf16(x)
    mid = as_bf16(x - hi)
    return hi, mid, as_bf16(x - hi - mid)


def _stack(*parts):
    return jnp.concatenate(parts, axis=-1).astype(_BF16)


def _index_operands(q, k, w):
    """q [B, S, H, D], k [B, S, D], w [B, S, H] float32 → the kernels'
    operands: ``qa`` = [q_hi | q_mid], ``qb`` = [q_hi | q_lo] as [B, H, S, 2D]
    bfloat16, ``ks`` = [k_mid | k_hi | k_hi | k_mid | k_lo | k_hi] as
    [B, S, 6D] bfloat16, ``w`` as [B, H, S] float32."""
    q_hi, q_mid, q_lo = _bf16_parts(q.transpose(0, 2, 1, 3))
    k_hi, k_mid, k_lo = _bf16_parts(k)
    return (_stack(q_hi, q_mid), _stack(q_hi, q_lo),
            _stack(k_mid, k_hi, k_hi, k_mid, k_lo, k_hi), w.transpose(0, 2, 1))


# a product of bfloat16 parts: ONE pass, whatever the package's default precision
_mm = functools.partial(lax.dot_general, precision=lax.Precision.DEFAULT,
                        preferred_element_type=_F32)


def _tile_products(qa, qb, kb, ka, kc):
    """A head's products with a key block, transposed: [Bk, Bq] float32,
    the six partial products in three passes, the smallest summed first."""
    return _mm(kc, qb, _att._NT) + _mm(kb, qa, _att._NT) + _mm(ka, qa, _att._NT)


def _index_fwd_kernel(qa_ref, qb_ref, ks_ref, w_ref, out_ref, *, scale):
    """qa_ref / qb_ref [1, H, Bq, 2D] bfloat16 (the query block's heads),
    ks_ref [1, Bk, 6D] bfloat16, w_ref [1, H, Bq] float32, out_ref [1, Bq, Bk]
    float32: ``scale · Σ_h w[h] · relu(q_h · k)``, ``-inf`` past the diagonal."""
    i, j = _pl.program_id(1), _pl.program_id(2)
    heads, block_q, d2 = qa_ref.shape[1:]
    block_k = ks_ref.shape[1]
    live = j * block_k <= i * block_q + block_q - 1

    @_pl.when(jnp.logical_not(live))                          # wholly past the diagonal
    def _():
        out_ref[0] = jnp.full((block_q, block_k), -jnp.inf, _F32)

    @_pl.when(live)
    def _():
        kb, ka, kc = (ks_ref[0, :, n * d2:(n + 1) * d2] for n in range(3))

        def head(h, total):
            st = _tile_products(qa_ref[0, h], qb_ref[0, h], kb, ka, kc)
            return total + jnp.maximum(st, 0.0) * w_ref[0, _pl.ds(h, 1), :]

        total = lax.fori_loop(0, heads, head, jnp.zeros((block_k, block_q), _F32))
        t = i * block_q + lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        s = j * block_k + lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        out_ref[0] = jnp.where(t >= s, total.T * scale, -jnp.inf)


def _g_parts(g):
    """:func:`_bf16_parts` inside a kernel: the conversions round."""
    hi = g.astype(_BF16)
    rest = g - hi.astype(_F32)
    mid = rest.astype(_BF16)
    return hi, mid, (rest - mid.astype(_F32)).astype(_BF16)


def _index_bwd_kernel(qa_ref, qb_ref, ks_ref, w_ref, di_ref, dq_ref, dk_ref, dw_ref):
    """The forward's operands and di_ref [1, Bq, Bk] float32 → dq_ref [1, H,
    Bq, 2D], dk_ref [1, S, 2D], dw_ref [1, H, Bq] float32, all without the
    scale, dq and dk as the two halves whose sum is the gradient.  dq and dw
    are the query block's, summed over its key blocks; dk is the batch row's,
    resident while its whole grid goes by.  ``g_h = dI · w[h] · [q_h · k >
    0]``; ``dq_h += g_h k``, ``dk += g_hᵀ q_h``, ``dw[h] = Σ_s dI · relu(q_h ·
    k)``: each live tile's products once more, then two products a head."""
    i, j = _pl.program_id(1), _pl.program_id(2)
    heads, block_q, d2 = qa_ref.shape[1:]
    block_k = ks_ref.shape[1]

    @_pl.when((i == 0) & (j == 0))
    def _():
        dk_ref[...] = jnp.zeros(dk_ref.shape, _F32)

    @_pl.when(j == 0)
    def _():
        dq_ref[...] = jnp.zeros(dq_ref.shape, _F32)
        dw_ref[...] = jnp.zeros(dw_ref.shape, _F32)

    @_pl.when(j * block_k <= i * block_q + block_q - 1)
    def _():
        kb, ka, kc = (ks_ref[0, :, n * d2:(n + 1) * d2] for n in range(3))
        t = i * block_q + lax.broadcasted_iota(jnp.int32, (block_k, block_q), 1)
        s = j * block_k + lax.broadcasted_iota(jnp.int32, (block_k, block_q), 0)
        di = jnp.where(t >= s, di_ref[0].T, 0.0)                       # [Bk, Bq]
        head_row = lax.broadcasted_iota(jnp.int32, (heads, block_q), 0)
        first_half = lax.broadcasted_iota(jnp.int32, (1, d2), 1) < d2 // 2

        def head(h, carry):
            dk, dw = carry
            qa, qb = qa_ref[0, h], qb_ref[0, h]
            st = _tile_products(qa, qb, kb, ka, kc)
            dw_h = jnp.sum(di * jnp.maximum(st, 0.0), axis=0, keepdims=True)
            g_hi, g_mid, g_lo = _g_parts(
                jnp.where(st > 0.0, di * w_ref[0, _pl.ds(h, 1), :], 0.0))
            # against [x_hi | x_mid]: both halves of g_hi's and g_mid's, the
            # first of g_lo's; against the lo stack: g_hi's lo half
            dq_ref[0, h] += (
                jnp.where(first_half, _mm(g_lo, ka, _att._TN) + _mm(g_hi, kc, _att._TN), 0.0)
                + _mm(g_mid, ka, _att._TN) + _mm(g_hi, ka, _att._TN))
            dk = dk + (jnp.where(first_half, _mm(g_lo, qa, _att._NN), _mm(g_hi, qb, _att._NN))
                       + _mm(g_mid, qa, _att._NN) + _mm(g_hi, qa, _att._NN))
            return dk, dw + jnp.where(head_row == h, dw_h, 0.0)

        dk, dw = lax.fori_loop(
            0, heads, head,
            (jnp.zeros((block_k, d2), _F32), jnp.zeros((heads, block_q), _F32)))
        rows = _pl.ds(_pl.multiple_of(j * block_k, block_k), block_k)
        dk_ref[0, rows, :] += dk
        dw_ref[0] += dw


def _index_launch(q, k, w, blocks, semantics):
    """What both kernels' launches share: the operands, their block specs,
    the key block a cell reads — a tile wholly past the diagonal asks for the
    query block's last live one again, so nothing is fetched for it — and the
    compiler's parameters."""
    h, d = q.shape[2:]
    bq, bk = blocks
    seen = lambda i, j: jnp.minimum(j, (i * bq + bq - 1) // bk)
    heads = _pl.BlockSpec((1, h, bq, 2 * d), lambda b, i, j: (b, 0, i, 0))
    specs = [heads, heads,
             _pl.BlockSpec((1, bk, 6 * d), lambda b, i, j: (b, seen(i, j), 0)),
             _pl.BlockSpec((1, h, bq), lambda b, i, j: (b, 0, i))]
    params = _pltpu.CompilerParams(dimension_semantics=semantics,
                                   vmem_limit_bytes=_INDEX_VMEM_LIMIT)
    return _index_operands(q, k, w), specs, seen, params


@functools.partial(jax.jit, static_argnames=("blocks", "interpret"))
def _index_fwd_pallas(q, k, w, *, blocks, interpret=False):
    """q [B, S, H, D], k [B, S, D], w [B, S, H] float32 → [B, S, S] float32."""
    b, s, h, d = q.shape
    bq, bk = blocks
    operands, specs, _, params = _index_launch(
        q, k, w, blocks, ("parallel", "parallel", "arbitrary"))
    return _pl.pallas_call(
        functools.partial(_index_fwd_kernel, scale=(h * d) ** -0.5),
        out_shape=jax.ShapeDtypeStruct((b, s, s), _F32),
        grid=(b, s // bq, s // bk),
        in_specs=specs,
        out_specs=_pl.BlockSpec((1, bq, bk), lambda b, i, j: (b, i, j)),
        interpret=interpret,
        name="index_scores_fwd",
        compiler_params=params,
    )(*operands)


@functools.partial(jax.jit, static_argnames=("blocks", "interpret"))
def _index_bwd_pallas(q, k, w, di, *, blocks, interpret=False):
    """The forward's operands and ``di`` [B, S, S] float32 (anything past
    the diagonal is ignored) → (dq, dk, dw) float32, shaped as q, k, w."""
    b, s, h, d = q.shape
    bq, bk = blocks
    operands, specs, seen, params = _index_launch(
        q, k, w, blocks, ("parallel", "arbitrary", "arbitrary"))
    dq, dk, dw = _pl.pallas_call(
        _index_bwd_kernel,
        out_shape=(jax.ShapeDtypeStruct((b, h, s, 2 * d), _F32),
                   jax.ShapeDtypeStruct((b, s, 2 * d), _F32),
                   jax.ShapeDtypeStruct((b, h, s), _F32)),
        grid=(b, s // bq, s // bk),
        in_specs=specs + [_pl.BlockSpec((1, bq, bk), lambda b, i, j: (b, i, seen(i, j)))],
        out_specs=(specs[0],
                   _pl.BlockSpec((1, s, 2 * d), lambda b, i, j: (b, 0, 0)),
                   specs[3]),
        interpret=interpret,
        name="index_scores_bwd",
        compiler_params=params,
    )(*operands, di)
    scale = (h * d) ** -0.5
    halves = lambda a: (a[..., :d] + a[..., d:]) * scale
    return halves(dq).transpose(0, 2, 1, 3), halves(dk), dw.transpose(0, 2, 1) * scale


def _launched(entry, launch, *arrays):
    """``entry`` (one of the two above) where and how ``launch`` says."""
    return _att._on_mesh(launch, functools.partial(
        entry, blocks=launch.blocks, interpret=launch.interpret), *arrays)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _index_scores_kernels(q, k, w, launch):
    """:func:`index_scores` in the Pallas kernels, forward and backward:
    float32 operands whose length ``launch.blocks`` divide."""
    return _launched(_index_fwd_pallas, launch, q, k, w)


def _index_scores_kernels_fwd(q, k, w, launch):
    return _index_scores_kernels(q, k, w, launch), (q, k, w)


def _index_scores_kernels_bwd(launch, res, di):
    return _launched(_index_bwd_pallas, launch, *res, di)


_index_scores_kernels.defvjp(_index_scores_kernels_fwd, _index_scores_kernels_bwd)


def _index_path(q_idx, cq, ck):
    """THE predicate of :func:`index_scores`: the kernels' :class:`_Launch`,
    or None for the XLA tiles.  Decided once a call from what the operands
    and the trace show, as ``attention._kernel_path`` decides for attention:
    the platform (the interpreter only by name), chunks that divide the
    length and that Mosaic can tile (the tile is turned inside the kernels:
    128 rows and columns at a time), a head width whose pair fills whole
    128-lane columns, VMEM for the blocks, and the mesh the trace is for (no
    compiler partitions a Mosaic kernel: a batch split over ``dp`` / ``fsdp``
    launches under a ``shard_map``, any other split takes the XLA tiles)."""
    b, s, h, d = q_idx.shape
    use, interpret = _att._use_pallas(q_idx)
    if not use or s % cq or s % ck:
        return None
    if not interpret:
        # two [H, Bq, 2D] bf16 stacks and dq's float32 one, dk's [S, 2D], the
        # [Bq, Bk] float32 tiles of dI: all double-buffered; ~8 tiles of
        # values; 16 MiB of the limit left to the compiler
        vmem = 2 * (2 * h * cq * 2 * d * 2 + h * cq * 2 * d * 4 + s * 2 * d * 4
                    + cq * ck * 4) + 8 * cq * ck * 4
        if cq % 128 or ck % 128 or (2 * d) % 128 or vmem > _INDEX_VMEM_LIMIT - (16 << 20):
            return None
    where = _att._rows_split(b)
    return None if where is None else _att._Launch(interpret, (cq, ck), *where)


@register("lightning_index_scores")
def index_scores(q_idx, k_idx, w, q_chunk=512, kv_chunk=512):
    """The indexer's scores of every (query, key) pair of a sequence.

    ``q_idx`` [B, S, H, D] (H index heads), ``k_idx`` [B, S, D] (ONE index key
    a position, shared by the heads), ``w`` [B, S, H] (each query's weight of
    each head) → ``[B, S, S]`` float32, ``I[b, t, s] = H^-½ Σ_j w[b, t, j] ·
    relu(q_idx[b, t, j] · k_idx[b, s] · D^-½)`` for ``s ≤ t`` and ``-inf``
    past the diagonal.  Computed in float32 whatever arrives (products of six
    bfloat16 partial products, float32 sums), in tiles of ``q_chunk ×
    kv_chunk``, a tile wholly past the diagonal not computed: on a TPU in two
    Pallas kernels that keep a tile's per-head products in VMEM
    (:func:`_index_path` says when), else as XLA tiles (a scan over query
    chunks of a scan over key chunks; a length no chunk divides is padded
    inside).  Differentiable in all three operands (each tile's products are
    computed again in the backward pass)."""
    from .. import profiler

    s = q_idx.shape[1]
    cq, ck = min(int(q_chunk), s), min(int(kv_chunk), s)
    q, k, w = (a.astype(_F32) for a in (q_idx, k_idx, w))
    launch = _index_path(q, cq, ck)
    if launch is None:
        profiler.incr("index_scores_dispatch_xla")
        return _index_scores_tiles(q, k, w, cq, ck)
    profiler.incr("index_scores_dispatch_pallas")
    return _index_scores_kernels(q, k, w, launch)


# ---------------------------------------------------------------------------
# The selection
# ---------------------------------------------------------------------------


def _ordered_bits(x):
    """float32 → uint32 whose unsigned order is the floats' order."""
    bits = lax.bitcast_convert_type(x + 0.0, jnp.uint32)       # -0.0 → +0.0
    return jnp.where(bits >> 31 == 1, ~bits, bits | jnp.uint32(1 << 31))


def _select_rows(scores, first_row, topk):
    """``scores`` [B, C, S] float32 (``-inf`` past the diagonal), its first
    query's position → the rows' selection, bool [B, C, S]."""
    s = scores.shape[-1]
    col = lax.broadcasted_iota(jnp.int32, scores.shape, 2)
    row = first_row + lax.broadcasted_iota(jnp.int32, scores.shape[:2] + (1,), 1)
    visible = col <= row
    key = jnp.where(visible, _ordered_bits(scores), jnp.uint32(0))
    selects = row >= topk            # the rows that see more than topk keys

    def count(hit):
        return jnp.sum(hit, axis=-1, keepdims=True, dtype=jnp.int32)

    def raise_bit(n, floor):
        """Keep bit 31 - n of the threshold if ``topk`` keys still pass it."""
        lifted = floor | (jnp.uint32(1) << (31 - n).astype(jnp.uint32))
        return jnp.where(count(key >= lifted) >= topk, lifted, floor)

    def lowest_tied(tied, owed):
        """The ``owed`` tied keys of lowest index: the greatest column ``c``
        with fewer than ``owed`` tied keys before it is the last one taken."""
        def raise_col(n, c):
            lifted = c | (jnp.int32(1) << (s.bit_length() - 1 - n))
            return jnp.where(count(tied & (col < lifted)) < owed, lifted, c)

        last = lax.fori_loop(0, s.bit_length(), raise_col, jnp.zeros_like(row))
        return tied & (col <= last)

    def best():
        # the topk-th largest key of each selecting row, bit by bit
        kth = lax.fori_loop(0, 32, raise_bit, jnp.zeros(row.shape, jnp.uint32))
        above, tied = key > kth, key == kth
        owed = topk - count(above)                             # ≥ 1 of the tied
        taken = lax.cond(jnp.any(selects & (count(tied) > owed)),
                         lowest_tied, lambda tied, owed: tied, tied, owed)
        return jnp.where(selects, above | taken, visible)

    # a chunk none of whose rows sees more than topk keys selects all it sees
    return lax.cond(first_row + scores.shape[1] <= topk, lambda: visible, best)


def select_topk(scores, topk, q_chunk=512):
    """Each query's ``topk`` highest-scored visible keys.

    ``scores`` [B, S, S] float32 with ``-inf`` past the diagonal
    (:func:`index_scores`) → int8 ``[B, S, S]``, 1 where query ``t`` selects
    key ``s``: exactly ``min(t + 1, topk)`` keys a row, all of them visible,
    the set ``jax.lax.top_k`` gives (equal scores: the lower index).  No
    sort: the ``topk``-th largest score of a row is found bit by bit, 32
    passes that each count the keys at or above a threshold, over ``q_chunk``
    rows at a time.  No gradient passes through a selection.

    While ``S ≤ 4 · topk`` the result carries the name :data:`KEEP_SELECT`
    (``jax.ad_checkpoint.checkpoint_name``; the identity outside a
    ``jax.checkpoint``): a layer checkpoint whose policy saves the name
    (``gluon.model_zoo.decoder.run_layer``) keeps the selection and its
    backward pass does not bisect again.  The rule weighs bytes: a query's
    row of the selection is ``S`` bytes, and at ``4 · topk`` (8,192 for the
    published 2,048) that is what its row of the attention's output takes,
    which the same checkpoint keeps.  Past it the bytes grow with ``S²``, the
    mask is mostly zeros and the thing to keep would be a compact form (a
    row's threshold and its tie count), which is not built: the selection is
    found again."""
    from jax.ad_checkpoint import checkpoint_name

    b, s = scores.shape[:2]
    topk = int(topk)
    if topk >= s:          # every query selects all it sees
        return jnp.tril(jnp.ones((s, s), jnp.int8))[None].repeat(b, 0)
    cq = min(int(q_chunk), s)
    padded = _pad_rows(lax.stop_gradient(scores).astype(_F32), 1, cq)

    def rows(i, chunk):
        return i + 1, _select_rows(chunk, i * cq, topk).astype(jnp.int8)

    _, out = lax.scan(rows, 0, _chunks(padded, 1, cq))
    select = jnp.moveaxis(out, 0, 1).reshape(b, -1, s)[:, :s]
    if s <= _KEEP_SELECT_MAX_ROW * topk:
        select = checkpoint_name(select, KEEP_SELECT)
    return select


def live_tiles(select, q_chunk=512, kv_chunk=512):
    """``(live, causal)``: how many ``q_chunk × kv_chunk`` tiles of the score
    matrix hold at least one selected pair, and how many lie at or below the
    diagonal (all of which a kernel that knows only ``causal`` computes), over
    the batch; float32 scalars.  Their ratio is what skipping dead tiles could
    save."""
    b, s = select.shape[:2]
    cq, ck = min(int(q_chunk), s), min(int(kv_chunk), s)
    padded = _pad_rows(_pad_rows(select, 1, cq), 2, ck)
    nq, nk = padded.shape[1] // cq, padded.shape[2] // ck
    live = (padded.reshape(b, nq, cq, nk, ck) != 0).any(axis=(2, 4))
    causal = sum(1 for i in range(nq) for j in range(nk)
                 if j * ck <= min(i * cq + cq, s) - 1)
    return jnp.sum(live, dtype=_F32), jnp.asarray(float(b * causal), _F32)


# ---------------------------------------------------------------------------
# The indexer's target: the main attention's probabilities, averaged over heads
# ---------------------------------------------------------------------------


def _head_probs_kernel(q_ref, k_ref, lse_ref, select_ref, out_ref, *, scale, group):
    """One (batch row, query block, key block) cell.  q_ref [1, H, Bq, D],
    k_ref [1, Hkv, Bk, D], lse_ref [1, 1, H, Bq] float32, select_ref / out_ref
    [1, Bk, Bq]: the tile is held TRANSPOSED, keys down and queries across,
    as the blockwise backward holds it, so each head's log-sum-exp is a row
    that broadcasts down the tile.  A head's ``exp(score - lse)`` of a key the
    query did not select may be anything (inf included); the select discards
    it after the sum."""
    i, j = _pl.program_id(1), _pl.program_id(2)
    heads, block_q = q_ref.shape[1], q_ref.shape[2]
    block_k = k_ref.shape[2]
    prec = _HIGHEST if q_ref.dtype == _F32 else lax.Precision.DEFAULT

    @_pl.when(j * block_k > i * block_q + block_q - 1)      # wholly past the diagonal
    def _():
        out_ref[0] = jnp.zeros(out_ref.shape[1:], _F32)

    @_pl.when(j * block_k <= i * block_q + block_q - 1)
    def _():
        def head(h, total):
            st = lax.dot_general(k_ref[0, h // group], q_ref[0, h], _att._NT,
                                 precision=prec, preferred_element_type=_F32) * scale
            return total + jnp.exp(st - lse_ref[0, 0, _pl.ds(h, 1), :])

        total = lax.fori_loop(0, heads, head, jnp.zeros((block_k, block_q), _F32))
        chosen = select_ref[0].astype(jnp.int32) != 0
        out_ref[0] = jnp.where(chosen, total * (1.0 / heads), 0.0)


@functools.partial(jax.jit, static_argnames=("scale", "blocks", "interpret"))
def _head_probs_pallas(q, k, lse, select, *, scale, blocks, interpret=False):
    """q [B, H, S, D], k [B, Hkv, S, D], lse [B, H, S] float32, select [B, S,
    S] int8 → the head-averaged probabilities [B, S, S] float32."""
    b, h, s, d = q.shape
    h_kv = k.shape[1]
    bq, bk = blocks
    nq = s // bq
    lse = lse.reshape(b, h, nq, bq).transpose(0, 2, 1, 3)      # [B, nq, H, Bq]
    out_t = _pl.pallas_call(
        functools.partial(_head_probs_kernel, scale=scale, group=h // h_kv),
        out_shape=jax.ShapeDtypeStruct((b, s, s), _F32),
        grid=(b, nq, s // bk),
        in_specs=[
            _pl.BlockSpec((1, h, bq, d), lambda b, i, j: (b, 0, i, 0)),
            _pl.BlockSpec((1, h_kv, bk, d), lambda b, i, j: (b, 0, j, 0)),
            _pl.BlockSpec((1, 1, h, bq), lambda b, i, j: (b, i, 0, 0)),
            _pl.BlockSpec((1, bk, bq), lambda b, i, j: (b, j, i)),
        ],
        out_specs=_pl.BlockSpec((1, bk, bq), lambda b, i, j: (b, j, i)),
        interpret=interpret,
        compiler_params=_pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=64 << 20),
    )(q, k, lse, select.transpose(0, 2, 1))
    return out_t.transpose(0, 2, 1)


def _head_probs_xla(q, k, select, scale, q_chunk):
    """The same from the plain expression, ``q_chunk`` queries at a time:
    q [B, S, H, D], k [B, S, Hkv, D] → [B, S, S] float32."""
    b, s, h, _ = q.shape
    group = h // k.shape[2]
    k = jnp.repeat(k, group, axis=2) if group > 1 else k
    prec = _HIGHEST if q.dtype == _F32 else lax.Precision.DEFAULT
    cq = min(int(q_chunk), s)

    def rows(_, chunk):
        qc, chosen = chunk
        sc = jnp.einsum("bqhd,bkhd->bhqk", qc, k, precision=prec,
                        preferred_element_type=_F32) * scale
        # a padded row selects nothing: keep its softmax finite
        sc = jnp.where(chosen[:, None] != 0, sc, -jnp.inf)
        top = jnp.max(sc, axis=-1, keepdims=True)
        e = jnp.exp(sc - jnp.where(jnp.isneginf(top), 0.0, top))
        p = e / jnp.maximum(e.sum(-1, keepdims=True), 1e-30)
        return None, p.mean(axis=1)

    _, out = lax.scan(rows, None, (_chunks(_pad_rows(q, 1, cq), 1, cq),
                                   _chunks(_pad_rows(select, 1, cq), 1, cq)))
    return jnp.moveaxis(out, 0, 1).reshape(b, -1, s)[:, :s]


def head_mean_probs(q, k, select, scale, lse=None, launch=None, q_chunk=512):
    """The main attention's probabilities averaged over its heads, ``p[b, t,
    s] = (1/H) Σ_h softmax_{s ∈ S_t}(q[t, h] · k[s, h // group] · scale)``:
    ``[B, S, S]`` float32, zero outside the selection ``select`` (int8 [B, S,
    S]), each row summing to one.  q [B, S, H, D], k [B, S, Hkv, D] as the
    attention read them.  With the kernels' log-sum-exp ``lse`` [B, H, S] and
    their ``launch`` (``_attend_bshd(..., with_lse=True)``) a Pallas kernel
    rebuilds each head's probabilities tile by tile; without, the plain
    expression ``q_chunk`` queries at a time.  Detached: the indexer's target
    carries no gradient."""
    q, k = lax.stop_gradient(q), lax.stop_gradient(k)
    if lse is None:
        return _head_probs_xla(q, k, select, scale, q_chunk)
    t = lambda x: x.transpose(0, 2, 1, 3)
    return _att._on_mesh(
        launch, functools.partial(_head_probs_pallas, scale=float(scale),
                                  blocks=launch.blocks, interpret=launch.interpret),
        t(q), t(k), lax.stop_gradient(lse), select)


# ---------------------------------------------------------------------------
# The indexer's loss
# ---------------------------------------------------------------------------


@register("indexer_kl_loss")
def indexer_kl_loss(scores, select, target):
    """``mean over (b, t) of KL(target[b, t, ·] ‖ softmax_{s ∈ S_t}
    scores[b, t, s])``: ``scores`` [B, S, S] float32 (the indexer's,
    differentiable), ``select`` [B, S, S] (non-zero: ``s ∈ S_t``), ``target``
    [B, S, S] (the head-averaged probabilities over ``S_t``, detached) → a
    float32 scalar.  A key whose target is zero adds nothing."""
    chosen = select != 0
    target = lax.stop_gradient(target.astype(_F32))
    # arithmetic only on finite numbers: the scores past the diagonal are -inf
    safe = jnp.where(chosen, scores.astype(_F32), 0.0)
    top = lax.stop_gradient(jnp.max(jnp.where(chosen, safe, -jnp.inf), axis=-1, keepdims=True))
    log_z = top + jnp.log(jnp.sum(jnp.where(chosen, jnp.exp(safe - top), 0.0),
                                  axis=-1, keepdims=True))
    held = chosen & (target > 0)
    log_target = jnp.log(jnp.where(held, target, 1.0))
    kl = jnp.sum(jnp.where(held, target * (log_target - safe + log_z), 0.0), axis=-1)
    return jnp.mean(kl)


# ---------------------------------------------------------------------------
# The layer
# ---------------------------------------------------------------------------


def _rotary_tables(positions, seq, dim, theta, sections):
    """Tables for ``dim`` rotated dims: text (``positions`` None: every
    stream is the token's index) takes the float64 NumPy tables, a constant
    of the program; given streams [3, B, S] are data."""
    if positions is None:
        return _att.yarn_rotary_tables(seq, dim, theta)
    if sections is not None:       # the split scaled to this many pairs
        total = sum(sections)
        sections = [n * (dim // 2) // total for n in sections]
    return _att.multi_stream_rotary_tables(positions, dim, theta, sections)


@register("sparse_attention")
def sparse_attention(x, qkv_weight, q_norm_gamma, k_norm_gamma, o_weight,
                     index_weight, index_norm_gamma, index_norm_beta,
                     positions=None, num_heads=1, kv_heads=1, head_dim=128,
                     index_heads=1, index_dim=64, topk=2048, q_chunk=512,
                     kv_chunk=512, eps=1e-6, rope_theta=10000.0,
                     mrope_section=None, scope="sparse_attention"):
    """Grouped-query causal self-attention over the keys an indexer selects,
    on ``x`` [B, S, d] (already normed): ``(out [B, S, d], index_loss,
    tiles_live, tiles_causal, selection [B, S, S] int8)``.

    Main path: ``[q | k | v] = x W_qkv`` (``num_heads`` query heads on
    ``kv_heads`` key/value heads of ``head_dim``; weights ``[out, in]``, no
    bias), RMSNorm over each head's dims of q and of k (``q_norm_gamma``,
    ``k_norm_gamma`` [head_dim]), rotary on all dims (rotate-half pairs,
    ``rope_theta``; ``positions`` [3, B, S] are three streams split over the
    frequency pairs by ``mrope_section``, None is text), the softmax over the
    selected keys, ``W_o``.

    Indexer, on ``stop_gradient(x)``: ``[q_I | k_I | w] = x̄ W_index``
    (``index_heads`` heads of ``index_dim``, one key, a weight a head),
    LayerNorm on ``k_I``, the same rotary on q_I and k_I,
    :func:`index_scores`, :func:`select_topk` with ``topk``.

    ``index_loss`` is :func:`indexer_kl_loss` against
    :func:`head_mean_probs`: it reaches the indexer's parameters only, the
    output's gradient everything else.  ``tiles_live`` / ``tiles_causal``:
    :func:`live_tiles` over ``q_chunk × kv_chunk`` tiles.  ``scope`` names
    the ``jax.named_scope``s: ``<scope>`` with ``.proj``, ``.index``,
    ``.select``, ``.core``, ``.index_loss``, ``.out`` beneath it."""
    from .. import profiler
    from .nn import layer_norm, rms_norm

    profiler.incr("sparse_attention_traced")
    b, s, _ = x.shape
    h, h_kv, dh = int(num_heads), int(kv_heads), int(head_dim)
    hi, di = int(index_heads), int(index_dim)
    prec = _HIGHEST if x.dtype == _F32 else lax.Precision.DEFAULT
    scale = dh ** -0.5

    def proj(a, weight):
        return jnp.einsum("...i,oi->...o", a, weight.astype(a.dtype), precision=prec)

    with jax.named_scope(scope):
        with jax.named_scope(scope + ".proj"):
            qkv = proj(x, qkv_weight)
            q = qkv[..., :h * dh].reshape(b, s, h, dh)
            k = qkv[..., h * dh:(h + h_kv) * dh].reshape(b, s, h_kv, dh)
            v = qkv[..., (h + h_kv) * dh:].reshape(b, s, h_kv, dh)
            cos, sin = _rotary_tables(positions, s, dh, rope_theta, mrope_section)
            q = _att.apply_rotary(rms_norm(q, q_norm_gamma, eps=eps), cos, sin, "half")
            k = _att.apply_rotary(rms_norm(k, k_norm_gamma, eps=eps), cos, sin, "half")

            idx = proj(lax.stop_gradient(x), index_weight).astype(_F32)
            q_idx = idx[..., :hi * di].reshape(b, s, hi, di)
            k_idx = layer_norm(idx[..., hi * di:(hi + 1) * di],
                               index_norm_gamma, index_norm_beta, eps=eps)
            w_idx = idx[..., (hi + 1) * di:]
            cos_i, sin_i = _rotary_tables(positions, s, di, rope_theta, mrope_section)
            q_idx = _att.apply_rotary(q_idx, cos_i, sin_i, "half")
            k_idx = _att.apply_rotary(k_idx[:, :, None, :], cos_i, sin_i, "half")[:, :, 0]
        with jax.named_scope(scope + ".index"):
            scores = index_scores(q_idx, k_idx, w_idx, q_chunk, kv_chunk)
        with jax.named_scope(scope + ".select"):
            select = select_topk(scores, topk, q_chunk)
            live, causal = live_tiles(select, q_chunk, kv_chunk)
        with jax.named_scope(scope + ".core"):
            out, lse, launch = _att._attend_bshd(q, k, v, True, scale, select=select,
                                                 with_lse=True)
        with jax.named_scope(scope + ".index_loss"):
            target = head_mean_probs(q, k, select, scale, lse, launch, q_chunk)
            index_loss = indexer_kl_loss(scores, select, target)
        with jax.named_scope(scope + ".out"):
            out = proj(out.reshape(b, s, h * dh), o_weight)
    sg = lax.stop_gradient
    return out, index_loss, sg(live), sg(causal), select
