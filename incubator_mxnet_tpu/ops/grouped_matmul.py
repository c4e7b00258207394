"""Grouped matrix products for the held experts (``ops/moe.py``): Pallas
kernels whose row tiles follow the group boundaries.

``rows`` sorted by group, ``group_sizes[g]`` of them to group ``g``, the rows
past the last group nobody's.  Three products, one layout:

* :func:`grouped_matmul` — ``out[r] = lhs[r] @ rhs[group(r)]``, ``[m, k] ×
  [G, k, n]``; with ``transpose_rhs`` the same product on ``rhs[g]ᵀ`` (``rhs``
  ``[G, n, k]``), which is the input gradient.
* :func:`grouped_matmul_wgrad` — ``out[g] = lhs[rows of g]ᵀ @ rhs[rows of
  g]``, ``[m, k], [m, n] → [G, k, n]``: the weight gradient.

The algorithm is the public one of MegaBlocks' grouped GEMM (Gale et al.
2022; ``jax.experimental.pallas.ops.tpu.megablox`` is its Pallas form): the
work is a list of VISITS ``(group, row tile)``, one for every row tile that
a group's rows touch, computed on the device from ``group_sizes`` and read by
the block index maps as scalar-prefetch operands.  The grid's visit axis ends
at the number of visits, so tiles that hold no routed row cost nothing; a
tile two groups share is visited once by each, and the kernel masks the rows
that are not the visiting group's.  Products accumulate in float32 in VMEM.
A width no 128 divides is the whole extent of a block, or an edge tile: the
output's edge is clipped by the block write, the contraction's edge is masked
in the last ``k`` tile.  What a kernel costs BESIDE its run time shaped the
rest (PERF.md §6 PR 33.5): a block's columns are worked 128 lanes at a time
by a loop inside the kernel, because one ``dot`` on a ``[2688, 1856]`` block
is a megabyte of unrolled instructions; and the entry points are jitted with
static tiles, because every ``pallas_call`` site is traced and lowered in
Python at every start of a program, whatever the compile cache holds.

Rows past the last group are ZERO in the tiles a group visits and UNDEFINED
in the others (as ``jax.lax.ragged_dot`` leaves them on the TPU); the caller
masks them.  Mosaic compiles the kernels for the TPU; ``interpret=True`` runs
them in the Pallas interpreter (the CPU test tier, by this argument only).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .attention import _LANE, _NN, _NT, _TN   # the lane width; a·b, a·bᵀ, aᵀ·b
from .nn import _zero_cotangent

__all__ = ["grouped_matmul", "grouped_matmul_wgrad", "grouped_dot", "row_tile"]

# a whole [k, n] expert weight in VMEM twice (the next group's arrives while
# this one's rows run) beside the row tiles: 29 MB at 3584 × 2048 bf16.  The
# compiler's default scoped limit is 16 MiB; a v5e core has 128 MiB.
_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "arbitrary", "arbitrary"),
    vmem_limit_bytes=100 << 20)


def row_tile(rows, groups=1):
    """The row tile the kernels take for ``rows`` sorted rows in ``groups``
    groups, or None where no tile divides them (the smallest row bucket: the
    caller keeps XLA).  256 rows, or 128 where that is more than half the
    mean group: a tile two groups share is worked through once for each, so
    long tiles on short groups spend the MXU on rows they mask away (v5e, a
    product alone: 384 rows a group 0.33 ms at 256, 0.34 at 128, 0.41 at 512;
    256 rows a group 0.37 at 128, 0.39 at 256, 0.53 at 512; PERF.md §5)."""
    fits = [t for t in (256, 128) if rows % t == 0]
    return next((t for t in fits if 2 * t * groups <= rows), fits[-1] if fits else None)


def _precision(dtype):
    return lax.Precision.HIGHEST if dtype == jnp.float32 else lax.Precision.DEFAULT


@functools.partial(jax.jit, static_argnames=("m", "tm", "empty_groups"))
def _visits(group_sizes, *, m, tm, empty_groups):
    """The work list: ``(offsets [G + 1], group of visit i, row tile of visit
    i, number of visits)``.  Visits are ordered by group, so also by tile; a
    group of no rows has none, or ONE where ``empty_groups`` (the weight
    gradient, which owes its block of zeros)."""
    groups, tiles_m = group_sizes.shape[0], m // tm
    ends = jnp.cumsum(group_sizes.astype(jnp.int32))
    starts = ends - group_sizes
    first = jnp.minimum(starts // tm, tiles_m - 1)
    count = jnp.where(group_sizes > 0, (ends - 1) // tm - first + 1,
                      1 if empty_groups else 0).astype(jnp.int32)
    upto = jnp.cumsum(count)
    i = jnp.arange(tiles_m + groups - 1 + (groups if empty_groups else 0),
                   dtype=jnp.int32)
    # the group whose visits include the i-th: how many groups end at or before it
    group = jnp.minimum((i[:, None] >= upto[None, :]).sum(1), groups - 1).astype(jnp.int32)
    tile = jnp.clip(first[group] + i - (upto[group] - count[group]),
                    0, tiles_m - 1).astype(jnp.int32)
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends])
    return offsets, group, tile, upto[-1]


def _own_rows(offsets, group, tile, visit, tm):
    """``[tm, 1]``: the rows of the visited tile that are the visiting group's."""
    g = group[visit]
    row = tile[visit] * tm + lax.broadcasted_iota(jnp.int32, (tm, 1), 0)
    return (row >= offsets[g]) & (row < offsets[g + 1])


def _only(x, keep):
    """``x`` where ``keep``, else 0 — in float32: the v5e's vector unit has no
    bf16 select."""
    return jnp.where(keep, x.astype(jnp.float32), 0).astype(x.dtype)


def _tiles(m, k, n, tiles):
    tm, tk, tn = tiles
    if m % tm:
        raise ValueError(f"{m} rows: no whole number of {tm}-row tiles")
    return tm, min(tk, k), min(tn, n)


_CHUNK = 128


def _each_chunk(width, body):
    """``body(columns)`` for a block's ``width`` columns, 256 lanes at a time,
    in a loop the compiler does NOT unroll (and one shorter chunk for what is
    left).  A product on a whole ``[2688, 1856]`` block, written as one
    ``dot``, is unrolled into ~1 MB of instructions; four layers' kernels in
    every row bucket were ~150 MB of program, 0.17 GB of HBM to hold
    (PERF.md §6 PR 33.5)."""
    whole = width // _CHUNK
    if whole:
        def step(j, carry):
            body(pl.ds(pl.multiple_of(j * _CHUNK, _CHUNK), _CHUNK))
            return carry
        lax.fori_loop(0, whole, step, 0)
    if width % _CHUNK:
        body(pl.ds(whole * _CHUNK, width % _CHUNK))


@functools.lru_cache(maxsize=None)
def _product_kernel(tm, tk, tn, tiles_k, k_edge, transpose_rhs, prec):
    """The forward / input-gradient kernel's body for these blocks: ONE
    function object a set of blocks, so that Pallas's own cache of traced
    kernels serves every row bucket and layer that takes the same blocks."""
    def kernel(offsets, group, tile, a_ref, w_ref, o_ref, *scratch):
        visit, k_i = pl.program_id(1), pl.program_id(2)
        last = k_i == tiles_k - 1
        # past the contraction's end lies anything: masked in its last tile
        inside = jnp.where(last & (k_edge > 0), k_edge, tk)
        a = a_ref[...]
        if k_edge:
            a = _only(a, lax.broadcasted_iota(jnp.int32, a.shape, 1) < inside)
        # the tile's other rows: what the group before wrote there, if this
        # tile was its last too; nothing yet (zero) otherwise
        own = _own_rows(offsets, group, tile, visit, tm)
        shared = (visit > 0) & (tile[jnp.maximum(visit - 1, 0)] == tile[visit])

        def columns(cols):
            w = w_ref[cols, :] if transpose_rhs else w_ref[:, cols]
            if k_edge:
                w = _only(w, lax.broadcasted_iota(
                    jnp.int32, w.shape, int(transpose_rhs)) < inside)
            acc = lax.dot_general(a, w, _NT if transpose_rhs else _NN, precision=prec,
                                  preferred_element_type=jnp.float32)
            if tiles_k > 1:
                acc_ref, = scratch
                acc = acc + jnp.where(k_i == 0, 0, acc_ref[:, cols])
                acc_ref[:, cols] = acc

            @pl.when(last)
            def _():
                others = jnp.where(shared, o_ref[:, cols].astype(jnp.float32), 0)
                o_ref[:, cols] = jnp.where(own, acc, others).astype(o_ref.dtype)

        _each_chunk(tn, columns)

    return kernel


# jitted: a call site of a shape already seen takes the traced kernel from
# jit's cache.  Tracing and lowering a kernel costs the chip's host 0.1–0.2 s,
# a step of four expert layers over three row buckets had 96 call sites of 24
# distinct shapes, and both are paid at every start, compile cache or not.
@functools.partial(jax.jit, static_argnames=("tiles", "transpose_rhs", "interpret"))
def grouped_matmul(lhs, rhs, group_sizes, *, tiles, transpose_rhs=False,
                   interpret=False):
    """``[m, k] × [G, k, n] → [m, n]`` (``rhs`` ``[G, n, k]`` with
    ``transpose_rhs``), row ``r`` on its group's matrix.  ``tiles`` ``(tm, tk,
    tn)``: ``tm`` divides ``m``; ``tk`` / ``tn`` are multiples of 128 or at
    least the whole of ``k`` / ``n``."""
    m, k = lhs.shape
    n = rhs.shape[1] if transpose_rhs else rhs.shape[2]
    tm, tk, tn = _tiles(m, k, n, tiles)
    tiles_k, tiles_n, k_edge = pl.cdiv(k, tk), pl.cdiv(n, tn), k % tk
    offsets, group, tile, visits = _visits(group_sizes, m=m, tm=tm, empty_groups=False)
    kernel = _product_kernel(tm, tk, tn, tiles_k, k_edge, transpose_rhs,
                             _precision(lhs.dtype))

    def w_index(n_i, v, k_i, offsets, group, tile):
        return (group[v], n_i, k_i) if transpose_rhs else (group[v], k_i, n_i)

    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((m, n), lhs.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(tiles_n, visits, tiles_k),
            in_specs=[
                pl.BlockSpec((tm, tk), lambda n_i, v, k_i, o, g, t: (t[v], k_i)),
                pl.BlockSpec((None, tn, tk) if transpose_rhs else (None, tk, tn),
                             w_index)],
            out_specs=pl.BlockSpec((tm, tn), lambda n_i, v, k_i, o, g, t: (t[v], n_i)),
            scratch_shapes=([pltpu.VMEM((tm, tn), jnp.float32)]
                            if tiles_k > 1 else [])),
        compiler_params=_PARAMS, interpret=interpret,
        name="grouped_matmul_nt" if transpose_rhs else "grouped_matmul",
    )(offsets, group, tile, lhs, rhs)


@functools.lru_cache(maxsize=None)
def _wgrad_kernel(tm, tn, prec):
    """The weight-gradient kernel's body (cached as :func:`_product_kernel`'s)."""
    def kernel(offsets, group, tile, a_ref, b_ref, o_ref, acc_ref):
        visit = pl.program_id(2)
        g = group[visit]
        before = jnp.maximum(visit - 1, 0)
        after = jnp.minimum(visit + 1, pl.num_programs(2) - 1)
        first = (visit == 0) | (group[before] != g)
        final = (visit == pl.num_programs(2) - 1) | (group[after] != g)
        own = _own_rows(offsets, group, tile, visit, tm)
        a = _only(a_ref[...], own)

        def columns(cols):
            acc = lax.dot_general(a, _only(b_ref[:, cols], own), _TN, precision=prec,
                                  preferred_element_type=jnp.float32)
            acc = acc + jnp.where(first, 0, acc_ref[:, cols])
            acc_ref[:, cols] = acc

            @pl.when(final)
            def _():
                o_ref[:, cols] = acc.astype(o_ref.dtype)

        _each_chunk(tn, columns)

    return kernel


@functools.partial(jax.jit, static_argnames=("tiles", "interpret", "out_dtype"))
def grouped_matmul_wgrad(lhs, rhs, group_sizes, *, tiles, interpret=False,
                         out_dtype=None):
    """``[m, k], [m, n] → [G, k, n]``: ``lhs[rows of g]ᵀ @ rhs[rows of g]``,
    zeros for a group of no rows.  ``tiles`` as in :func:`grouped_matmul`
    (``tk`` and ``tn`` tile the RESULT; the contraction runs over row tiles)."""
    (m, k), n = lhs.shape, rhs.shape[1]
    tm, tk, tn = _tiles(m, k, n, tiles)
    offsets, group, tile, visits = _visits(group_sizes, m=m, tm=tm, empty_groups=True)
    kernel = _wgrad_kernel(tm, tn, _precision(lhs.dtype))

    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((group_sizes.shape[0], k, n),
                                       out_dtype or lhs.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(pl.cdiv(k, tk), pl.cdiv(n, tn), visits),
            in_specs=[
                pl.BlockSpec((tm, tk), lambda k_i, n_i, v, o, g, t: (t[v], k_i)),
                pl.BlockSpec((tm, tn), lambda k_i, n_i, v, o, g, t: (t[v], n_i))],
            out_specs=pl.BlockSpec((None, tk, tn),
                                   lambda k_i, n_i, v, o, g, t: (g[v], k_i, n_i)),
            scratch_shapes=[pltpu.VMEM((tk, tn), jnp.float32)]),
        compiler_params=_PARAMS, interpret=interpret,
        name="grouped_matmul_wgrad",
    )(offsets, group, tile, lhs, rhs)


# ---------------------------------------------------------------------------
# The product with its derivative
# ---------------------------------------------------------------------------


_VMEM_BUDGET = 72 << 20   # of the 100 MiB the kernels ask for


def _halved(width):
    """Half of ``width`` in whole 128-lane tiles."""
    return max(_LANE, -(-width // (2 * _LANE)) * _LANE)


def _pick_tiles(m, k, n, groups, itemsize, wgrad=False):
    """``(tm, tk, tn)`` for ``[m, k] × [G, k, n]``.  A group's whole ``[k,
    n]`` matrix where VMEM holds it — it is then read from HBM once, and a
    row tile's product is one MXU pass with no float32 sum revisited (a v5e
    reads 0.35 ms a product of either decoder cell so, 0.44–0.56 at 1024 ×
    1024 and 512 × 512 tiles) — else the result's width halved until it does
    (the weight gradient: the contraction, its result's rows)."""
    tm, tk, tn = row_tile(m, groups), k, n
    if wgrad:   # result [tk, tn] twice and its float32 sum, the row tiles twice
        held = lambda: tk * tn * (2 * itemsize + 4) + 2 * tm * (tk + tn) * itemsize
        while held() > _VMEM_BUDGET and tk > _LANE:
            tk = _halved(tk)
    else:       # operands and result twice, the float32 product
        held = lambda: 2 * (tm * tk + tk * tn + tm * tn) * itemsize + 8 * tm * tn
        while held() > _VMEM_BUDGET and tn > _LANE:
            tn = _halved(tn)
    return tm, tk, tn


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def grouped_dot(a, w, group_sizes, interpret=False):
    """``jax.lax.ragged_dot(a, w, group_sizes)`` by the kernels above: ``[m,
    k] × [G, k, n] → [m, n]`` in ``a``'s dtype, float32 accumulation (float32
    operands at ``Precision.HIGHEST``).  Its derivative is the two other
    kernels: the input gradient is the same product on ``wᵀ``, the weight
    gradient the per-group ``aᵀ g``, in ``w``'s dtype.  Rows past the last
    group: see the module's note — result and input gradient are the caller's
    to mask.  ``row_tile(m)`` must not be None."""
    (m, k), (groups, _, n) = a.shape, w.shape
    return grouped_matmul(a, w, group_sizes, interpret=interpret,
                          tiles=_pick_tiles(m, k, n, groups, a.dtype.itemsize))


def _grouped_dot_fwd(a, w, group_sizes, interpret):
    return grouped_dot(a, w, group_sizes, interpret), (a, w, group_sizes)


def _grouped_dot_bwd(interpret, res, g):
    a, w, group_sizes = res
    (m, k), (groups, _, n) = a.shape, w.shape
    g, size = g.astype(a.dtype), a.dtype.itemsize
    da = grouped_matmul(g, w, group_sizes, transpose_rhs=True, interpret=interpret,
                        tiles=_pick_tiles(m, n, k, groups, size))
    dw = grouped_matmul_wgrad(a, g, group_sizes, interpret=interpret, out_dtype=w.dtype,
                              tiles=_pick_tiles(m, k, n, groups, size, wgrad=True))
    return da, dw, _zero_cotangent(group_sizes)


grouped_dot.defvjp(_grouped_dot_fwd, _grouped_dot_bwd)
