"""Fused scaled-dot-product attention — the TPU answer to cuDNN fused
attention (the reference has no fused attention at all; its transformer
support lived out-of-repo in GluonNLP.  SURVEY.md §5 marks this as the one
area where this framework intentionally EXCEEDS the reference).

Four paths; the dispatchers (:func:`flash_attention`, ``_attend_bshd``,
:func:`fused_qkv_attention`) choose ONE for a call from what its operands'
shapes say (``_kernel_path``, crossovers measured on a v5e: PERF.md §6, PR
29), and the forward, the VJP forward and the backward of that call all
belong to it:

1. **One-tile Pallas kernels that read the fused QKV projection in place**
   (``_flash_qkv_tile``): self-attention whose whole S×S tile fits VMEM (S
   up to 512) and whose heads fill 128-lane columns of ``[B, S, 3·H·Dh]``
   (Dh 32, 64 or 128).  No head transpose; scores, probabilities and their
   gradients live and die in VMEM; the backward computes the scores and the
   exponentials once.
2. **Blockwise Pallas flash kernels** (``_flash_kernels``; compiled by
   Mosaic on TPU; the Pallas interpreter only when asked for by name,
   ``MXNET_TPU_FLASH=interpret`` — the CPU test tier does): online-softmax
   forward — queries tiled over the grid, K/V streamed through VMEM in
   ``block_k`` chunks — and the one-pass backward (`_flash_bwd_pallas`: keys
   tiled over the grid, the head's queries resident, dq summed in VMEM), so
   the S×S score matrix is never materialized in HBM and memory stays
   linear in S.  Accumulation in fp32 on the MXU
   (``preferred_element_type``), inputs may be bf16.  Want ``[B·H, S, Dh]``
   physically, so ``[B, S, H, Dh]`` callers pay two transposes.  A causal
   call whose blocks tile a square score matrix with an even number of them
   takes the FOLDED kernels (``_folds``) where the head's rows they keep
   resident fit in VMEM: a grid cell computes query blocks i and n-1-i (the
   backward: key blocks j and n-1-j), n + 1 block steps in a loop whose trip
   count the compiler knows, two steps a loop body.
3. **XLA path** (short sequences, float16, lengths no block divides,
   non-TPU backends, ``MXNET_TPU_FLASH=off``): same math as one fused jnp
   expression with a rematerialized backward; in the ``[B, S, H, Dh]``
   layout (``_flash_bshd``) the head split/merge is a free reshape of the
   QKV matmul output and XLA folds the remaining dimension shuffles into
   the attention dot_generals (docs/PERF_NOTES.md round-3 win).  Its S×S
   float32 temporaries make a round trip through HBM each, which is what
   the crossover weighs against the kernels' fixed costs.
4. **Ring attention** (``parallel/ring.py``) for sequence-parallel long
   context — built on the same online-softmax update.

Gradients: ``jax.custom_vjp`` on every path — the backward recomputes
attention probabilities from the saved (q, k, v) (the kernels: and the
output and the log-sum-exp, one float32 a query row), so no S×S residual is
stored *between* fwd and bwd.  The blockwise path's forward rules name the two
arrays their kernel wrote (``jax.ad_checkpoint.checkpoint_name``:
:data:`KEEP_OUT`, :data:`KEEP_LSE`).  Outside a ``jax.checkpoint`` a name is
the identity; under one whose policy saves these names
(``gluon.model_zoo.decoder.run_layer``) the two arrays are kept from the
forward pass, every output of the kernel is then known to the recomputed
forward, and the kernel does not run a second time.

On a mesh: no compiler partitions a Mosaic kernel, so where the trace is
for several devices (``parallel.mesh_scope``, which ``SPMDTrainer`` opens
round a step's trace) the dispatchers launch the kernels under a
``shard_map`` over the batch axes, and leave a mesh that splits the model
(tp, sp, pp, ep) to the XLA path (``_rows_split``).  A jit over several
devices that publishes no mesh gets JAX's lowering error for the kernels
from S 256 up; ``mesh_scope(mesh)`` round the call is the way out.
"""
from __future__ import annotations

import functools
import math
import os
import typing

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as _pl
from jax.experimental.pallas import tpu as _pltpu
from jax.sharding import PartitionSpec

__all__ = ["flash_attention", "attention_reference", "latent_attention",
           "yarn_rotary_tables", "multi_stream_rotary_tables", "apply_rotary",
           "rotary_embedding"]

# The forward kernel keeps one head's whole K and V in VMEM beside its
# 512-wide tiles, the backward the head's Q, dO, dq and dq's float32 sum: 22 MB
# at S 4096 with 192-wide keys, 36 MB at S 16384 and 70 MB at S 32768 with
# 64-wide ones.  The folded kernels keep the head's whole rows of the blocks
# they visit too (the forward Q, O and the log-sum-exp; the backward K, V, dK
# and dV), and a selection's rows of both blocks of a cell: 40 MB for the
# forward at S 8192 under an int8 selection, and are taken only where those
# rows fit (``_folds``).  The compiler's default scoped
# limit is 16 MiB; a v5e core has 128 MiB.
_MOSAIC_PARAMS = _pltpu.CompilerParams(vmem_limit_bytes=48 << 20)
# The kernels whose grid cells share blocks resident across the inner axis (the
# one-pass backward's dq; the folded kernels' head rows) run its cells in order.
_RESIDENT_PARAMS = _pltpu.CompilerParams(
    dimension_semantics=("parallel", "arbitrary"), vmem_limit_bytes=100 << 20)

# TPU lane width: row statistics (lse) are replicated across a 128-lane
# trailing dim so their blocks satisfy Mosaic's (8, 128) tiling rule.
_LANE = 128

# What the blockwise forward rules call the arrays their kernel wrote
# (``checkpoint_name``): the output [B, H, S, Dv] and one lane of the
# log-sum-exp, float32 [B·H, S].  A checkpoint whose policy saves these names
# keeps them, and its recomputed forward has no kernel left to run.
KEEP_OUT = "attn.core.out"
KEEP_LSE = "attn.core.lse"

# dot_general dimension numbers of the kernels' 2-D products
_NT = (((1,), (1,)), ((), ()))   # a · bᵀ
_NN = (((1,), (0,)), ((), ()))   # a · b
_TN = (((0,), (0,)), ((), ()))   # aᵀ · b


def _use_pallas(x=None):
    """(use the kernel, run it in the interpreter).  ``on`` means the
    COMPILED kernel wherever the call lands — off-TPU that is a lowering
    error, not a quiet switch to the interpreter; interpret mode is only
    ever asked for by name."""
    mode = os.environ.get("MXNET_TPU_FLASH", "auto")
    if mode == "off":
        return False, False
    if mode == "interpret":
        return True, True
    if mode == "on":
        return True, False
    from ..util import resolve_platform

    return resolve_platform(x) == "tpu", False  # auto


_BATCH_AXES = ("dp", "fsdp")  # parallel/sharding.py: the axes a batch is split over


class _Launch(typing.NamedTuple):
    """How the kernels of one attention call are launched; static for the
    call's ``custom_vjp``, so its forward and backward agree."""
    interpret: bool
    blocks: tuple | None = None   # the blockwise kernels' (block_q, block_k)
    mesh: typing.Any = None       # launch under a shard_map over ...
    axes: tuple = ()              # ... these batch axes of it


def _axis_bound(name):
    try:
        lax.axis_size(name)
        return True
    except NameError:
        return False


def _rows_split(batch):
    """Where a kernel launch has to be placed on the mesh this trace is for
    (``parallel.mesh_scope``; ``SPMDTrainer`` scopes its own while it traces
    a step).  The compiler cannot partition a Mosaic kernel by itself — a
    jit over several devices fails to lower one ("wrap the call in a
    shard_map") — but attention works on each batch row alone, so:
    ``(None, ())``: launch as it is (no mesh, one device, or already
    inside a ``shard_map`` over the mesh); ``(mesh, axes)``: launch under a
    ``shard_map`` over the batch axes; ``None``: the kernels cannot run
    (other axes split the model, or the batch does not divide): XLA path."""
    from ..parallel.mesh import current_mesh

    mesh = current_mesh()
    if mesh is None or mesh.size == 1:
        return None, ()
    split = [a for a in mesh.axis_names if mesh.shape[a] > 1]
    bound = [a for a in split if _axis_bound(a)]
    if bound:
        return (None, ()) if bound == split else None
    if set(split) <= set(_BATCH_AXES) and batch % mesh.size == 0:
        return mesh, tuple(split)
    return None


def _on_mesh(launch, fn, *arrays):
    """``fn(*arrays)`` — arrays and results all lead with the batch (or
    batch·heads) dimension — where ``launch`` places it."""
    if launch.mesh is None:
        return fn(*arrays)
    rows = PartitionSpec(launch.axes)
    return jax.shard_map(fn, mesh=launch.mesh, in_specs=(rows,) * len(arrays),
                         out_specs=rows, check_vma=False)(*arrays)


# ---------------------------------------------------------------------------
# Pallas forward kernel
# ---------------------------------------------------------------------------


def online_softmax_update(o, m, l, s, v, matmul):
    """One blockwise online-softmax accumulation step (parallel/ring.py's,
    whose blocks can be fully masked; the Pallas kernel below takes
    :func:`_live_softmax_update`).  ``m``/``l`` carry a trailing
    keepdim; ``s`` may contain -inf for masked entries; fully-masked rows
    keep zero mass (caller fixes l==0 before the final divide)."""
    m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
    m_safe = jnp.where(jnp.isneginf(m_new), 0.0, m_new)
    p = jnp.exp(s - m_safe)
    p = jnp.where(jnp.isneginf(s), 0.0, p)
    corr = jnp.where(jnp.isneginf(m), 0.0, jnp.exp(m - m_safe))
    l_new = l * corr + p.sum(axis=-1, keepdims=True)
    o_new = o * corr + matmul(p, v)
    return o_new, m_new, l_new


def _live_softmax_update(o, m, l, s, v, matmul):
    """:func:`online_softmax_update` where every row has a live key among
    the blocks seen so far, this one included — the kernels below: without
    a mask no key is dead, and under the causal one every row sees key 0 in
    its first block.  The new maximum is then finite, a masked score's
    ``exp(-inf - m)`` and the first block's ``exp(-inf - m)`` correction are
    exact zeros, and none of the guards is needed: the same bits for five
    vector operations a score fewer."""
    m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m - m_new)
    return o * corr + matmul(p, v), m_new, l * corr + p.sum(axis=-1, keepdims=True)


def _precision(dtype):
    """Matmul precision pinned per dtype: the package-global 'highest'
    default would request an fp32 contraction on bf16 operands, which Mosaic
    rejects."""
    return (jax.lax.Precision.HIGHEST if dtype == jnp.float32
            else jax.lax.Precision.DEFAULT)  # bf16 AND fp16 operands


def _fwd_step(q, k, v, carry, q_start, k_start, chosen, *, causal, scale):
    """One key block of a query block's online softmax: ``carry`` (m, l,
    acc) after the keys ``k`` [Bk, D] / ``v`` [Bk, Dv] from position
    ``k_start`` on, for the queries ``q`` [Bq, D] from ``q_start`` on.
    ``chosen`` [Bq, Bk] int8 or None is the selection's tile."""
    m, l, acc = carry
    block_q, block_k = q.shape[0], k.shape[0]
    prec = _precision(q.dtype)
    s = jax.lax.dot_general(
        q, k, _NT, preferred_element_type=jnp.float32, precision=prec,
    ) * scale  # [Bq, Bk], fp32 accumulate then scale
    if causal:
        q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        s = jnp.where(q_pos >= k_pos, s, -jnp.inf)
    if chosen is not None:
        s = jnp.where(chosen.astype(jnp.int32) != 0, s, -jnp.inf)
    update = _live_softmax_update if chosen is None else online_softmax_update
    acc_new, m_new, l_new = update(
        acc, m, l, s, v,
        lambda p, v: jax.lax.dot_general(
            p.astype(v.dtype), v, _NN,
            preferred_element_type=jnp.float32, precision=prec,
        ),
    )
    return m_new, l_new, acc_new


def _fwd_init(block_q, d_v):
    return (jnp.full((block_q, 1), -jnp.inf, jnp.float32),
            jnp.zeros((block_q, 1), jnp.float32),
            jnp.zeros((block_q, d_v), jnp.float32))


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, block_k, causal, scale,
                select_ref=None):
    """One (batch·head, q-block) grid cell: stream K/V blocks, online
    softmax in fp32.  Shapes: q_ref [1, Bq, D], k_ref [1, Sk, D],
    v_ref [1, Sk, Dv] (Dv may differ from D: latent attention has 192-wide
    queries and keys and 128-wide values).  ``select_ref`` [1, Bq, Sk] int8,
    if given, is the batch row's selection for these queries, shared by its
    heads: a score is live only where it is non-zero (and causal-visible).
    A row's first blocks may then hold no live key, so the update is the
    guarded :func:`online_softmax_update`; every row must select a key
    somewhere (the caller's contract), or its result is undefined.

    Operands stay in their input dtype (bf16 rides the MXU at full rate)
    with fp32 accumulation via preferred_element_type (:func:`_precision`).

    Under the causal mask every block a cell computes is masked, those wholly
    below the diagonal too: the compare and select hide under the MXU's
    work, and a second, unmasked loop for those blocks measured SLOWER on a
    v5e (PERF.md §6, PR 31)."""
    i = _pl.program_id(1)
    block_q = q_ref.shape[1]
    nk = k_ref.shape[1] // block_k
    q = q_ref[0]  # [Bq, D], native dtype

    def body(j, carry):
        keys = _pl.ds(j * block_k, block_k)
        chosen = None if select_ref is None else select_ref[0, :, keys]
        return _fwd_step(q, k_ref[0, keys, :], v_ref[0, keys, :], carry,
                         i * block_q, j * block_k, chosen, causal=causal, scale=scale)

    if causal:
        # Skip K/V blocks entirely in the masked future: q-block i only
        # attends to k positions < (i+1)*block_q (halves FLOPs/bandwidth
        # for decoder self-attention vs. streaming all nk blocks).
        nk_bound = jnp.minimum(nk, ((i + 1) * block_q + block_k - 1) // block_k)
    else:
        nk_bound = nk
    m, l, acc = lax.fori_loop(0, nk_bound, body, _fwd_init(block_q, v_ref.shape[2]))
    o_ref[0] = (acc / l).astype(o_ref.dtype)
    if lse_ref is not None:
        # log-sum-exp per query row, saved for the blockwise backward:
        # p = exp(s - lse) reproduces softmax without re-running the
        # online rescaling.  Replicated across a 128-lane trailing dim to
        # satisfy TPU tiling (same layout as jax's reference TPU kernel).
        lse_ref[0] = jnp.broadcast_to(m + jnp.log(l), lse_ref.shape[1:])


# ---------------------------------------------------------------------------
# The causal triangle folded.  Where a causal call's blocks tile a square score
# matrix (Sq == Sk, one block size, an even number n of blocks), grid cell i
# takes query block i and query block n-1-i (in the backward: key block j and
# key block n-1-j), which together hold n+1 blocks at or below the diagonal,
# whatever i: ONE loop whose trip count the compiler knows, where a cell of the
# unfolded kernels loops a number of times it reads from its grid index.  The
# step index says which block pair a step computes and where the second block
# starts, so every step is the same code.  The forward visits a query row's
# key blocks in the unfolded order, so its bits are the unfolded kernel's; the
# backward sums dq in another order.
# ---------------------------------------------------------------------------


def _lanes(rows, width, dtype):
    """VMEM bytes of a [rows, width] block: its last dimension in whole
    128-lane tiles."""
    return rows * -(-width // _LANE) * _LANE * jnp.dtype(dtype).itemsize


def _folded_vmem_bytes(s, block, d, d_v, dtype, group, selected):
    """The VMEM the folded kernels' blocks take, the larger of the forward's
    and the backward's: every block double-buffered, the backward's float32
    dq sum once.  The forward holds the head's Q, K, V, O and log-sum-exp,
    the backward its Q, K, V, dO, dQ, dK and dV (float32 where grouped heads
    sum theirs after the kernel) and the query rows' statistics; both the
    int8 rows of a cell's two blocks under a selection."""
    f32 = jnp.float32
    sel = 2 * _lanes(block, s, jnp.int8) if selected else 0
    fwd = 2 * _lanes(s, d, dtype) + 2 * _lanes(s, d_v, dtype) + _lanes(s, _LANE, f32) + sel
    grad = f32 if group > 1 else dtype
    bwd = (3 * _lanes(s, d, dtype) + 2 * _lanes(s, d_v, dtype) + _lanes(s, d, grad)
           + _lanes(s, d_v, grad) + 2 * _lanes(s // block, block, f32) + sel)
    return max(2 * fwd, 2 * bwd + _lanes(s, d, f32))


# The folded kernels' blocks must leave the steps' tiles (scores,
# probabilities and their gradients, a few MB) room under _RESIDENT_PARAMS's
# limit.  Compiled for a v5e: S 16384 with 64-wide heads, 64 MB of blocks by
# _folded_vmem_bytes, fits; S 32768 (128 MB) and S 16384 with 192-wide keys
# (104 MB) do not.
_FOLDED_VMEM_BYTES = 84 << 20


def _folds(causal, sq, sk, blocks, d, d_v, dtype, group=1, selected=False):
    """Does this blockwise call take the folded kernels?  From its shapes:
    causal, a square score matrix, one block size (``blocks``: query, key),
    an even number of blocks, and the head's rows the cells keep resident
    within :data:`_FOLDED_VMEM_BYTES` (``group`` query heads a key/value
    head, ``selected`` under an int8 selection); else the unfolded kernels,
    whose cells keep the head's keys (forward) or queries (backward) only."""
    block_q, block_k = blocks
    n = sq // block_q
    return (causal and sq == sk and block_q == block_k and n >= 2 and n % 2 == 0
            and _folded_vmem_bytes(sq, block_q, d, d_v, dtype, group, selected)
            <= _FOLDED_VMEM_BYTES)


# Steps a loop body of the folded kernels.  Measured on a v5e at the keye
# cell's shape (32 query heads on 4 key/value heads of 128, S 8192, an int8
# selection), forward / backward ms a call (PERF.md §6): unfolded 6.23 /
# 10.52; folded, one step a body 6.00 / 10.14, two 5.56 / 9.70, three 5.34 /
# 9.61, the loop unrolled 5.06 / 9.40; the unfolded kernels at two steps a body
# (their count read from the grid index, the odd step under a cond) 5.72 /
# 10.12, 60 % of the folded pair's gain (21 % at xing's shape, 58-65 % at
# nemotron's and lfm2's).  The compiled kernel holds a copy of the
# step for each step a body and each step left over ((n + 1) mod this), so an
# unrolled loop's code grows with n (11.5 x the forward's at S 8192) and every
# call site of a program carries it; two steps a body take 3 copies whatever n.
_FOLD_STEPS_A_BODY = 2


def _run_steps(steps, step, carry):
    """``carry = step(t, carry)`` for t in 0..steps-1: the steps left over
    first, then a loop of ``_FOLD_STEPS_A_BODY`` steps a body, so that the
    compiler schedules each step's products beside its neighbour's vector
    work."""
    per = _FOLD_STEPS_A_BODY
    head = steps % per
    for t in range(head):
        carry = step(t, carry)

    def body(r, carry):
        for u in range(per):
            carry = step(head + r * per + u, carry)
        return carry

    return lax.fori_loop(0, steps // per, body, carry)


def _fwd_folded_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, block, scale,
                       select_refs=None):
    """Grid cell (batch·head, i) of a folded causal forward: query block i
    over key blocks 0..i, then query block n-1-i over key blocks 0..n-1-i, n
    + 1 steps.  q_ref, o_ref and lse_ref hold the head's whole sequence
    (resident while its cells go by), k_ref / v_ref as in
    :func:`_fwd_kernel`; ``select_refs`` (the selection's rows of query
    block i, of query block n-1-i), each [1, Bq, Sk] int8, or None."""
    i = _pl.program_id(1)
    n = k_ref.shape[1] // block
    init = _fwd_init(block, v_ref.shape[2])

    def body(t, carry):
        first = t <= i                              # still on query block i
        qb = jnp.where(first, i, n - 1 - i)
        kb = jnp.where(first, t, t - i - 1)
        # the second query block starts from the online softmax's start
        carry = tuple(jnp.where(t == i + 1, a, c) for a, c in zip(init, carry))
        rows = _pl.ds(_pl.multiple_of(qb * block, block), block)
        keys = _pl.ds(_pl.multiple_of(kb * block, block), block)
        chosen = None
        if select_refs is not None:
            lo, hi = select_refs
            # both tiles read and one selected: a cond that reads one splits
            # the loop body, and measured slower (keye's forward 5.53 -> 6.05 ms)
            chosen = jnp.where(first, lo[0, :, keys], hi[0, :, keys])
        m, l, acc = carry = _fwd_step(
            q_ref[0, rows, :], k_ref[0, keys, :], v_ref[0, keys, :], carry,
            qb * block, kb * block, chosen, causal=True, scale=scale)

        @_pl.when((t == i) | (t == n))              # a query block's last key block
        def _():
            o_ref[0, rows, :] = (acc / l).astype(o_ref.dtype)
            if lse_ref is not None:
                lse_ref[0, rows, :] = jnp.broadcast_to(m + jnp.log(l),
                                                       (block, lse_ref.shape[2]))

        return carry

    _run_steps(n + 1, body, init)


def _kv_row(rows, kv_rows):
    """Query row (batch·head) → the key/value row it reads.  With as many
    key/value heads as query heads it is the row itself, and the index maps
    are the ones they always were."""
    if rows % kv_rows:
        raise ValueError(f"{rows} query rows do not divide over {kv_rows} "
                         f"key/value rows")
    group = rows // kv_rows
    return (lambda b: b) if group == 1 else (lambda b: b // group)


def _flash_fwd_pallas(q, k, v, causal, scale, interpret, block_q=128, block_k=128,
                      with_lse=False, select=None):
    """q: [BH, S, D], k: [BHkv, Sk, D], v: [BHkv, Sk, Dv] (batch·heads
    flattened; grouped-query heads have BH = group · BHkv and query row ``r``
    reads key/value row ``r // group``: the block index does it, nothing is
    repeated in memory).  ``with_lse=True`` also returns the per-row
    log-sum-exp [BH, S, 128] for the blockwise backward.  ``select`` [B, S,
    Sk] int8 is a selection shared by the heads of a batch row (query row
    ``r`` reads row ``r // (BH / B)`` of it); without one the call is what it
    always was."""
    bh, sq, d = q.shape
    sk, dv = k.shape[1], v.shape[2]
    kv_row = _kv_row(bh, k.shape[0])
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    if sq % block_q or sk % block_k:
        raise ValueError(f"sequence lengths ({sq},{sk}) must divide blocks ({block_q},{block_k})")
    n = sq // block_q
    folded = _folds(causal, sq, sk, (block_q, block_k), d, dv, q.dtype, bh // k.shape[0],
                    select is not None)
    if folded:   # grid cell i: query blocks i and n-1-i, the head's rows resident
        grid, params = (bh, n // 2), _RESIDENT_PARAMS
        rows = lambda w: _pl.BlockSpec((1, sq, w), lambda b, i: (b, 0, 0))
        selected = (lambda b, i: i, lambda b, i: n - 1 - i)
    else:
        grid, params = (bh, n), _MOSAIC_PARAMS
        rows = lambda w: _pl.BlockSpec((1, block_q, w), lambda b, i: (b, i, 0))
        selected = (lambda b, i: i,)
    in_specs = [rows(d),
                _pl.BlockSpec((1, sk, d), lambda b, i: (kv_row(b), 0, 0)),
                _pl.BlockSpec((1, sk, dv), lambda b, i: (kv_row(b), 0, 0))]
    operands = (q, k, v)
    if select is not None:
        batch_row = _kv_row(bh, select.shape[0])    # query row → batch row
        in_specs += [_pl.BlockSpec((1, block_q, sk),
                                   lambda b, i, at=at: (batch_row(b), at(b, i), 0))
                     for at in selected]
        operands += (select,) * len(selected)
    n_select = len(operands) - 3

    def kernel(q_ref, k_ref, v_ref, *refs):
        selects, (o_ref, *lse_ref) = refs[:n_select] or None, refs[n_select:]
        lse_ref = lse_ref[0] if lse_ref else None
        if folded:
            _fwd_folded_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, block=block_q,
                               scale=scale, select_refs=selects)
        else:
            _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, block_k=block_k, causal=causal,
                        scale=scale, select_ref=selects and selects[0])

    out_shape = (jax.ShapeDtypeStruct((bh, sq, dv), q.dtype),
                 jax.ShapeDtypeStruct((bh, sq, _LANE), jnp.float32))
    out_specs = (rows(dv), rows(_LANE))
    if not with_lse:
        out_shape, out_specs = out_shape[0], out_specs[0]
    return _pl.pallas_call(
        kernel,
        out_shape=out_shape,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        interpret=interpret,
        compiler_params=params,
        name="flash_fwd_folded" if folded else "flash_fwd",
    )(*operands)


# ---------------------------------------------------------------------------
# Pallas backward kernel: one pass.  A (batch·head, key block) grid cell
# loops over the query blocks the causal bound leaves it and computes each
# block's scores, exponentials, dO·Vᵀ and dS ONCE, for all three gradients:
# 5 matrix products a block (a dq pass beside a dk/dv pass spends 7, and two
# passes of vector work).  dk and dv are the cell's own; dq is summed in a
# float32 VMEM scratch that lives across the head's key blocks (the grid's
# inner axis) and is scaled, cast and written once, so no partial dq goes
# through HBM and nothing accumulates in bf16.  The score tile is held
# TRANSPOSED, keys down and queries across (k·qᵀ): pᵀ·dO and dSᵀ·q are then
# plain products and only dq = dS·k contracts over a transposed left
# operand, and the query rows' statistics (log-sum-exp; delta = rowsum(dO ⊙
# O), one small XLA reduction a call) are row vectors, ``[BH, Sq/Bq, Bq]``
# float32, that broadcast down the tile.  The S×S score matrix never exists
# in HBM.
# ---------------------------------------------------------------------------


def _bwd_step(k, v, q_ref, do_ref, lse_ref, delta_ref, dq_acc, carry, qb, ahead, offset,
              chosen, *, scale):
    """Query block ``qb``'s share of one key block's gradients: ``carry``
    (dk, dv) float32 after it, its dq summed into ``dq_acc``.  ``ahead``
    [Bk, Bq] is query column less key row inside a block, ``offset`` the key
    block's first position less the query block's (None: no causal mask);
    ``chosen`` [Bk, Bq] int8 or None is the transposed selection's tile."""
    dk, dv = carry
    block_q = lse_ref.shape[2]
    mm = functools.partial(jax.lax.dot_general, precision=_precision(k.dtype),
                           preferred_element_type=jnp.float32)
    rows = _pl.ds(_pl.multiple_of(qb * block_q, block_q), block_q)
    q = q_ref[0, rows, :]
    do = do_ref[0, rows, :]
    lse = lse_ref[0, _pl.ds(qb, 1), :]          # [1, Bq] fp32
    delta = delta_ref[0, _pl.ds(qb, 1), :]
    st = mm(k, q, _NT) * scale                 # [Bk, Bq]: sᵀ
    if offset is not None:
        st = jnp.where(ahead >= offset, st, -jnp.inf)
    if chosen is not None:
        st = jnp.where(chosen.astype(jnp.int32) != 0, st, -jnp.inf)
    pt = jnp.exp(st - lse)                     # masked → exp(-inf) = 0
    dv = dv + mm(pt.astype(do.dtype), do, _NN)
    dpt = mm(v, do, _NT)
    dst = (pt * (dpt - delta)).astype(q.dtype)
    dk = dk + mm(dst, q, _NN)
    dq_acc[rows, :] += mm(dst, k, _TN)         # [Bq, D]
    return dk, dv


def _ahead(block_k, block_q):
    """Query column less key row inside a [Bk, Bq] block: q_pos - k_pos less
    the blocks' offsets.  Every causal block is masked, those wholly past the
    diagonal too: the compare and select hide under the MXU's work, and an
    unmasked loop of its own for them measured slower."""
    return (jax.lax.broadcasted_iota(jnp.int32, (block_k, block_q), 1)
            - jax.lax.broadcasted_iota(jnp.int32, (block_k, block_q), 0))


def _bwd_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dq_ref, dk_ref, dv_ref, dq_acc, *, block_q, causal, scale,
                select_ref=None):
    """q_ref [1, Sq, D], do_ref [1, Sq, Dv], lse_ref / delta_ref
    [1, Sq/Bq, Bq] and dq_ref [1, Sq, D] are the head's, resident while its
    key blocks go by; k_ref / dk_ref [1, Bk, D] and v_ref / dv_ref
    [1, Bk, Dv] the cell's; dq_acc [Sq, D] float32.  ``select_ref`` [1, Bk,
    Sq] int8, if given, is the batch row's selection TRANSPOSED (keys down,
    queries across, as the score tile is held): a dead score's probability
    is ``exp(-inf - lse)``, an exact zero, as under the causal mask."""
    j = _pl.program_id(1)
    block_k, d, d_v = k_ref.shape[1], k_ref.shape[2], v_ref.shape[2]
    nq = q_ref.shape[1] // block_q
    k = k_ref[0]                       # [Bk, D] native dtype
    v = v_ref[0]                       # [Bk, Dv]
    ahead = _ahead(block_k, block_q) if causal else None

    @_pl.when(j == 0)
    def _():
        dq_acc[...] = jnp.zeros(dq_acc.shape, jnp.float32)

    def body(i, carry):
        chosen = None if select_ref is None else select_ref[
            0, :, _pl.ds(_pl.multiple_of(i * block_q, block_q), block_q)]
        return _bwd_step(k, v, q_ref, do_ref, lse_ref, delta_ref, dq_acc, carry, i, ahead,
                         j * block_k - i * block_q if causal else None, chosen, scale=scale)

    # from the query block that holds this key block's first key on: the
    # blocks before it lie wholly in the masked future
    first = jnp.minimum(nq, (j * block_k) // block_q) if causal else 0
    dk, dv = lax.fori_loop(
        first, nq, body,
        (jnp.zeros((block_k, d), jnp.float32), jnp.zeros((block_k, d_v), jnp.float32)))
    dk_ref[0] = (dk * scale).astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)

    @_pl.when(j == _pl.num_programs(1) - 1)
    def _():
        dq_ref[0] = (dq_acc[...] * scale).astype(dq_ref.dtype)


def _bwd_folded_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                       dq_ref, dk_ref, dv_ref, dq_acc, *, block, scale, select_refs=None):
    """Grid cell (batch·head, j) of a folded causal backward: key block j
    over query blocks j..n-1, then key block n-1-j over query blocks
    n-1-j..n-1, n + 1 steps.  As :func:`_bwd_kernel`, but k_ref / v_ref and
    dk_ref / dv_ref hold the head's whole sequence (resident while its cells
    go by); ``select_refs`` (the transposed selection's rows of key block j,
    of key block n-1-j), each [1, Bk, Sq] int8, or None."""
    j = _pl.program_id(1)
    n = q_ref.shape[1] // block
    ahead = _ahead(block, block)
    init = (jnp.zeros((block, k_ref.shape[2]), jnp.float32),
            jnp.zeros((block, v_ref.shape[2]), jnp.float32))

    @_pl.when(j == 0)
    def _():
        dq_acc[...] = jnp.zeros(dq_acc.shape, jnp.float32)

    def body(t, carry):
        first = t < n - j                           # still on key block j
        kb = jnp.where(first, j, n - 1 - j)
        qb = jnp.where(first, j + t, t - 1)
        carry = tuple(jnp.where(t == n - j, a, c) for a, c in zip(init, carry))
        keys = _pl.ds(_pl.multiple_of(kb * block, block), block)
        chosen = None
        if select_refs is not None:
            lo, hi = select_refs
            queries = _pl.ds(_pl.multiple_of(qb * block, block), block)
            chosen = jnp.where(first, lo[0, :, queries], hi[0, :, queries])   # as the forward
        dk, dv = carry = _bwd_step(k_ref[0, keys, :], v_ref[0, keys, :], q_ref, do_ref, lse_ref,
                                   delta_ref, dq_acc, carry, qb, ahead, (kb - qb) * block,
                                   chosen, scale=scale)

        @_pl.when((t == n - j - 1) | (t == n))      # a key block's last query block
        def _():
            dk_ref[0, keys, :] = (dk * scale).astype(dk_ref.dtype)
            dv_ref[0, keys, :] = dv.astype(dv_ref.dtype)

        return carry

    _run_steps(n + 1, body, init)

    @_pl.when(j == _pl.num_programs(1) - 1)
    def _():
        dq_ref[0] = (dq_acc[...] * scale).astype(dq_ref.dtype)


def _flash_bwd_pallas(q, k, v, do, o, lse, causal, scale, interpret,
                      block_q=128, block_k=128, select=None):
    """q: [BH, S, D]; k: [BHkv, Sk, D]; v: [BHkv, Sk, Dv]; do/o: [BH, S,
    Dv]; lse: [BH, Sq] fp32, a value a query row → (dq, dk, dv).  With
    grouped-query heads each query head's cell writes ITS dk and dv, in
    float32, and the group's are summed after the kernel (one small XLA
    reduction a call).  ``select`` [B, Sq, Sk] int8 is the forward's
    selection; the kernel reads its transpose (one XLA transpose of an int8
    array a call)."""
    bh, sq, d = q.shape
    bkv, sk, d_v = k.shape[0], k.shape[1], v.shape[2]
    kv_row, group = _kv_row(bh, bkv), bh // bkv
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    nq, nk = sq // block_q, sk // block_k
    # the query rows' statistics as rows: the forward's log-sum-exp, and
    # rowsum(dO ⊙ O) once a row where the kernels used to recompute it (and
    # read o whole) for every key block
    lse = lse.reshape(bh, nq, block_q)
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1).reshape(bh, nq, block_q)

    head = lambda rows, w: _pl.BlockSpec((1, rows, w), lambda b, j: (b, 0, 0))
    folded = _folds(causal, sq, sk, (block_q, block_k), d, d_v, q.dtype, group,
                    select is not None)
    if folded:   # grid cell j: key blocks j and n-1-j, the head's keys resident
        grid = (bh, nk // 2)
        blk = lambda w: head(sk, w)
        kv_blk = lambda w: _pl.BlockSpec((1, sk, w), lambda b, j: (kv_row(b), 0, 0))
        selected = (lambda b, j: j, lambda b, j: nk - 1 - j)
    else:
        grid = (bh, nk)
        blk = lambda w: _pl.BlockSpec((1, block_k, w), lambda b, j: (b, j, 0))
        kv_blk = lambda w: _pl.BlockSpec((1, block_k, w), lambda b, j: (kv_row(b), j, 0))
        selected = (lambda b, j: j,)
    part = (lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype)) if group == 1 else (
        lambda a: jax.ShapeDtypeStruct((bh,) + a.shape[1:], jnp.float32))
    in_specs = [
        head(sq, d),                                              # q
        kv_blk(d),                                                # k
        kv_blk(d_v),                                              # v
        head(sq, d_v),                                            # do
        head(nq, block_q),                                        # lse
        head(nq, block_q),                                        # delta
    ]
    operands = (q, k, v, do, lse, delta)
    if select is not None:
        batch_row = _kv_row(bh, select.shape[0])
        in_specs += [_pl.BlockSpec((1, block_k, sq),
                                   lambda b, j, at=at: (batch_row(b), at(b, j), 0))
                     for at in selected]
        operands += (select.transpose(0, 2, 1),) * len(selected)
    n_select = len(operands) - 6

    def kernel(*refs):
        ins, selects, outs = refs[:6], refs[6:6 + n_select] or None, refs[6 + n_select:]
        if folded:
            _bwd_folded_kernel(*ins, *outs, block=block_q, scale=scale, select_refs=selects)
        else:
            _bwd_kernel(*ins, *outs, block_q=block_q, causal=causal, scale=scale,
                        select_ref=selects and selects[0])

    dq, dk, dv = _pl.pallas_call(
        kernel,
        out_shape=(jax.ShapeDtypeStruct(q.shape, q.dtype), part(k), part(v)),
        grid=grid,
        in_specs=in_specs,
        out_specs=(head(sq, d), blk(d), blk(d_v)),
        scratch_shapes=[_pltpu.VMEM((sq, d), jnp.float32)],
        interpret=interpret,
        compiler_params=_RESIDENT_PARAMS,
        name="flash_bwd_folded" if folded else "flash_bwd",
    )(*operands)
    if group > 1:
        dk, dv = (a.reshape((bkv, group) + a.shape[1:]).sum(axis=1).astype(like.dtype)
                  for a, like in ((dk, k), (dv, v)))
    return dq, dk, dv


# ---------------------------------------------------------------------------
# One-tile kernels on the fused QKV projection, read in place.  Up to S 512 a
# head's whole S×S tile is 1 MB of float32: scores, probabilities and their
# gradients are computed and consumed in VMEM, the backward computes the scores
# and the exponentials ONCE (5 matmuls, as the blockwise backward above), and
# nothing is transposed: ``[B, S, 3·H·Dh]`` is a whole number of
# 128-lane columns a head GROUP (two 64-wide heads), so a (1, S, 128) block of
# the projection's output hands a grid cell its heads where they lie, and the
# result is written as ``[B, S, H·Dh]``.  Inside a column the heads are told
# apart by lane masks, not slices: Q·Kᵀ contracts over all 128 lanes with the
# other heads' lanes of K zeroed, P·V and the three gradient products yield all
# 128 lanes and a select keeps the head's own — the MXU passes a 64-wide slice
# would need, and no lane shuffles.
# ---------------------------------------------------------------------------


def _group_heads(ref_shape, dh):
    """[(index in the column, lane mask or None)] of the heads that share
    one ``ref_shape`` = [S, 128] column of the projection."""
    group = ref_shape[1] // dh
    if group == 1:
        return [(0, None)]
    lane = lax.broadcasted_iota(jnp.int32, ref_shape, 1)
    return [(g, (lane >= g * dh) & (lane < (g + 1) * dh)) for g in range(group)]


def _only(x, mine):
    return x if mine is None else jnp.where(mine, x, jnp.zeros_like(x))


def _tile_scores(q, k, mine, causal, scale, prec):
    """float32 [S, S] scores of the head whose lanes ``mine`` marks."""
    s = lax.dot_general(q, _only(k, mine), _NT, precision=prec,
                        preferred_element_type=jnp.float32) * scale
    if causal:
        row = lax.broadcasted_iota(jnp.int32, s.shape, 0)
        col = lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(row >= col, s, -jnp.inf)  # the diagonal keeps every row alive
    return s


def _qkv_tile_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, dh, causal, scale):
    """One (batch row, 128-lane column of heads) grid cell.  q/k/v_ref: the
    column's (1, S, 128) blocks of the three parts of the projection.
    ``lse_ref`` (1, S, 128) float32 holds the row's log-sum-exp of head h in
    lane h, for ALL heads of the batch row: it stays in VMEM across the
    row's columns (the grid's inner axis) and each cell fills its lanes."""
    col = _pl.program_id(1)
    q, k, v = q_ref[0], k_ref[0], v_ref[0]
    prec = (lax.Precision.HIGHEST if q.dtype == jnp.float32
            else lax.Precision.DEFAULT)
    heads = _group_heads(q.shape, dh)
    lane = lax.broadcasted_iota(jnp.int32, q.shape, 1)
    if lse_ref is not None:
        @_pl.when(col == 0)
        def _():
            lse_ref[0] = jnp.zeros(lse_ref.shape[1:], jnp.float32)
        lse_row = lse_ref[0]
    out = None
    for g, mine in heads:
        s = _tile_scores(q, k, mine, causal, scale, prec)
        m = s.max(axis=-1, keepdims=True)
        p = jnp.exp(s - m)
        l = p.sum(axis=-1, keepdims=True)
        o = lax.dot_general(p.astype(v.dtype), v, _NN, precision=prec,
                            preferred_element_type=jnp.float32) / l
        out = o if out is None else jnp.where(mine, o, out)
        if lse_ref is not None:
            lse_row = jnp.where(lane == col * len(heads) + g, m + jnp.log(l), lse_row)
    o_ref[0] = out.astype(o_ref.dtype)
    if lse_ref is not None:
        lse_ref[0] = lse_row


def _qkv_tile_bwd_kernel(q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref,
                         dq_ref, dk_ref, dv_ref, *, dh, causal, scale):
    col = _pl.program_id(1)
    q, k, v, do = q_ref[0], k_ref[0], v_ref[0], do_ref[0]
    prec = (lax.Precision.HIGHEST if q.dtype == jnp.float32
            else lax.Precision.DEFAULT)
    mm = functools.partial(lax.dot_general, precision=prec,
                           preferred_element_type=jnp.float32)
    heads = _group_heads(q.shape, dh)
    lane = lax.broadcasted_iota(jnp.int32, q.shape, 1)
    lse_row = lse_ref[0]
    do_o = do.astype(jnp.float32) * o_ref[0].astype(jnp.float32)
    dq = dk = dv = None
    for g, mine in heads:
        s = _tile_scores(q, k, mine, causal, scale, prec)
        lse = jnp.sum(jnp.where(lane == col * len(heads) + g, lse_row, 0.0),
                      axis=-1, keepdims=True)
        p = jnp.exp(s - lse)                       # [S, S]; masked → 0
        delta = jnp.sum(_only(do_o, mine), axis=-1, keepdims=True)
        dv_g = mm(p.astype(do.dtype), do, _TN)      # [S, 128], own lanes valid
        dp = mm(do, _only(v, mine), _NT)
        ds = (p * (dp - delta)).astype(q.dtype)
        dq_g, dk_g = mm(ds, k, _NN), mm(ds, q, _TN)
        if dq is None:
            dq, dk, dv = dq_g, dk_g, dv_g
        else:
            dq, dk, dv = (jnp.where(mine, new, old) for new, old in
                          ((dq_g, dq), (dk_g, dk), (dv_g, dv)))
    dq_ref[0] = (dq * scale).astype(dq_ref.dtype)
    dk_ref[0] = (dk * scale).astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


# longest sequence whose tile the kernels above take whole: the backward holds
# about five float32 S×S tiles, 5 MB at 512 and 20 MB at 1024
_TILE_MAX_SEQ = 512

_TILE_PARAMS = _pltpu.CompilerParams(
    dimension_semantics=("parallel", "arbitrary"), vmem_limit_bytes=48 << 20)


def _qkv_tile_fits(s, heads, dh, dtype, interpret=False):
    """Whether self-attention from a fused ``[B, S, 3·heads·dh]`` projection
    can run in the one-tile kernels: heads that fill 128-lane columns, one
    log-sum-exp lane a head, a sequence that is one lane-aligned tile."""
    return (dh in (32, 64, 128) and (heads * dh) % _LANE == 0
            and heads <= _LANE and s % _LANE == 0 and s <= _TILE_MAX_SEQ
            and (interpret or dtype in (jnp.bfloat16, jnp.float32)))


def _qkv_tile_grid(qkv):
    """For a fused projection ``[B, S, 3·D]``: the (batch row, column of
    heads) grid, the BlockSpecs of the column of part 0/1/2 (q/k/v), of a
    column of a ``[B, S, D]`` array and of the batch row's log-sum-exp
    block, and the shape of a ``[B, S, D]`` array of the projection's dtype."""
    b, s, d3 = qkv.shape
    cols = d3 // 3 // _LANE

    def part(n):
        return _pl.BlockSpec((1, s, _LANE), lambda b, c: (b, 0, n * cols + c))
    return ((b, cols), [part(0), part(1), part(2)],
            _pl.BlockSpec((1, s, _LANE), lambda b, c: (b, 0, c)),
            _pl.BlockSpec((1, s, _LANE), lambda b, c: (b, 0, 0)),
            jax.ShapeDtypeStruct((b, s, d3 // 3), qkv.dtype))


def _qkv_tile_fwd(qkv, heads, causal, scale, interpret, with_lse):
    grid, qkv_specs, col_spec, lse_spec, out_shape = _qkv_tile_grid(qkv)
    static = dict(dh=out_shape.shape[2] // heads, causal=causal, scale=scale)
    if with_lse:
        kernel = functools.partial(_qkv_tile_fwd_kernel, **static)
        out_shape = (out_shape, jax.ShapeDtypeStruct(
            out_shape.shape[:2] + (_LANE,), jnp.float32))
        out_specs = (col_spec, lse_spec)
    else:
        def kernel(q_ref, k_ref, v_ref, o_ref):
            _qkv_tile_fwd_kernel(q_ref, k_ref, v_ref, o_ref, None, **static)
        out_specs = col_spec
    return _pl.pallas_call(
        kernel, out_shape=out_shape, grid=grid, in_specs=qkv_specs,
        out_specs=out_specs, interpret=interpret, compiler_params=_TILE_PARAMS,
    )(qkv, qkv, qkv)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3, 4))
def _flash_qkv_tile(qkv, heads, causal, scale, launch):
    """Self-attention ``[B, S, 3·H·Dh]`` → ``[B, S, H·Dh]`` in the one-tile
    kernels (callers check :func:`_qkv_tile_fits`)."""
    return _on_mesh(launch, lambda x: _qkv_tile_fwd(
        x, heads, causal, scale, launch.interpret, with_lse=False), qkv)


def _flash_qkv_tile_fwd(qkv, heads, causal, scale, launch):
    out, lse = _on_mesh(launch, lambda x: _qkv_tile_fwd(
        x, heads, causal, scale, launch.interpret, with_lse=True), qkv)
    return out, (qkv, out, lse)


def _qkv_tile_bwd(qkv, do, out, lse, heads, causal, scale, interpret):
    grid, qkv_specs, col_spec, lse_spec, grad_shape = _qkv_tile_grid(qkv)
    return _pl.pallas_call(
        functools.partial(_qkv_tile_bwd_kernel, dh=grad_shape.shape[2] // heads,
                          causal=causal, scale=scale),
        out_shape=(grad_shape,) * 3, grid=grid,
        in_specs=qkv_specs + [col_spec, col_spec, lse_spec],
        out_specs=(col_spec,) * 3,
        interpret=interpret, compiler_params=_TILE_PARAMS,
    )(qkv, qkv, qkv, do, out, lse)


def _flash_qkv_tile_bwd(heads, causal, scale, launch, res, do):
    qkv, out, lse = res
    grads = _on_mesh(launch, lambda *arrays: _qkv_tile_bwd(
        *arrays, heads, causal, scale, launch.interpret), qkv, do, out, lse)
    return (jnp.concatenate(grads, axis=-1),)


_flash_qkv_tile.defvjp(_flash_qkv_tile_fwd, _flash_qkv_tile_bwd)


# ---------------------------------------------------------------------------
# Reference path (XLA-fused) + custom VJP
# ---------------------------------------------------------------------------


def _live(s, causal, select=None, heads_axis=1):
    """Scores ``[B, H, Sq, Sk]`` with the dead ones at ``-inf``: those the
    causal mask hides and, under a selection ``[B, Sq, Sk]`` (non-zero =
    chosen, shared by the heads), those not chosen."""
    if causal:
        sq, sk = s.shape[-2], s.shape[-1]
        s = jnp.where(jnp.arange(sq)[:, None] >= jnp.arange(sk)[None, :], s, -jnp.inf)
    if select is not None:
        s = jnp.where(jnp.expand_dims(select, heads_axis) != 0, s, -jnp.inf)
    return s


def attention_reference(q, k, v, causal=False, scale=None, select=None):
    """Plain jnp attention: q/k/v [B, H, S, D] (or [BH, S, D]); ``select``
    [B, Sq, Sk] (with [B, H, S, D] operands) keeps only the chosen keys.

    Operands stay in their input dtype (bf16 rides the MXU at full rate)
    with fp32 accumulation via ``preferred_element_type``; only the softmax
    itself runs in fp32.  Upcasting the operands would halve MXU rate and
    double score-matrix HBM traffic for no accuracy the fp32 accumulate
    doesn't already provide."""
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    prec = (jax.lax.Precision.HIGHEST if q.dtype == jnp.float32
            else jax.lax.Precision.DEFAULT)
    s = jnp.einsum("...qd,...kd->...qk", q, k,
                   preferred_element_type=jnp.float32, precision=prec) * scale
    p = jax.nn.softmax(_live(s, causal, select), axis=-1)
    return jnp.einsum("...qk,...kd->...qd", p.astype(v.dtype), v,
                      preferred_element_type=jnp.float32,
                      precision=prec).astype(v.dtype)


# Largest block the Pallas kernels tile queries and keys by.  Measured on a
# v5e at [32 heads, S 4096, 192-wide keys, 128-wide values], bf16, causal,
# the kernels alone (PERF.md §6, PR 31): forward / backward 2.00 / 4.83 ms at
# 512 x 512 (queries x keys), 3.07 / 5.21 at 256 x 256, 2.02 / 5.11 at 256 x
# 512, 2.75 / 4.99 at 512 x 256, 2.27 / 4.90 at 1024 x 512, 2.11 / 5.08 at 512
# x 1024, 2.13 / 4.87 at 1024 x 1024; 128 x 128 three times slower forward.
_PALLAS_BLOCK_Q = 512
_PALLAS_BLOCK_K = 512


def _pallas_blocks(sq, sk, block_q=None, block_k=None):
    """Largest MXU-friendly blocks that evenly divide the sequence lengths,
    or None if none exists (→ fall back to the XLA path rather than crash
    on unpadded/bucketed lengths)."""
    sizes = (1024, 512, 256, 128, 64, 32, 16, 8)
    block_q, block_k = block_q or _PALLAS_BLOCK_Q, block_k or _PALLAS_BLOCK_K
    bq = next((b for b in sizes if b <= block_q and sq % b == 0), None)
    bk = next((b for b in sizes if b <= block_k and sk % b == 0), None)
    if bq is None or bk is None:
        return None
    return min(bq, sq), min(bk, sk)


# Where the kernels take over from the XLA path: measured on a v5e
# (tools/bench_longcontext.py; PERF.md §6, PR 29, re-read by PR 31 with the
# one-pass backward) on 8,192 tokens of twelve 64-wide bf16 heads read from a
# fused QKV projection, one layer's forward + backward in ms, head transposes
# included:
#
#        S    XLA   blockwise, blocks of 512 / 256 / 128   one tile in place
#      128   0.40        —    /   —   / 1.60                     0.60
#      256   0.93        —    / 1.32  / 1.99                     0.64
#      384   1.76        —    /   —   / 2.55                     0.67
#      512   2.41      1.24   / 1.80  / 3.22                     0.69
#     1024   5.24      1.86   / 2.92  / 5.71                      —
#     2048  10.14      3.00   / 5.11  / 10.66                     —
#
# The XLA path's float32 S×S temporaries make a round trip through HBM each,
# so its cost grows with S for the same tokens; a kernel's does not, but its
# fixed costs (transposes, per-block overheads) only pay from a length on, and
# only with blocks the MXU fills.
# Earlier thresholds (forward from 1024, backward from 8192) dated from 128 ×
# 128 blocks, which lose to XLA at every length above.
_TILE_MIN_SEQ = 256      # the one-tile kernels on a fused QKV projection
_KERNEL_MIN_SEQ = 512    # the blockwise kernels ...
_KERNEL_MIN_BLOCK = 256  # ... where a block at least this long divides the lengths
# ... and whatever the length, above this many bytes of float32 scores
# ([B, H, Sq, Sk]) the XLA path's S×S temporaries (scores, probabilities and
# their gradients, several copies) no longer fit beside a model: 32 heads at S
# 4096 are 2 GiB a copy.  The kernels keep memory linear.
_KERNEL_MIN_SCORE_BYTES = 1 << 30


def _kernel_path(q, k, seq_axis=2, qkv_heads=None):
    """THE predicate: which path takes this attention call — ``"tile"`` (the
    one-tile kernels; asked only by :func:`fused_qkv_attention`, which passes
    its head count as ``qkv_heads``), ``"blockwise"`` or ``"xla"``?  Made
    once a call by the dispatchers below from what the operands show —
    lengths, head width and count, float32 score bytes, dtype, the block
    that divides the lengths — and from the mesh the trace is for
    (:func:`_rows_split`); the forward, the VJP forward and the
    backward all follow it (each path is a ``custom_vjp`` of its own), so
    evaluation and training numerics cannot part.  ``seq_axis`` lets
    bshd-layout callers ask without materializing a transpose.
    Returns (path, the kernels' :class:`_Launch` or None)."""
    sq, sk = q.shape[seq_axis], k.shape[seq_axis]
    use, interpret = _use_pallas(q)
    if q.dtype == jnp.float16 and not interpret:
        use = False  # Mosaic has no f16; the XLA path handles it
    blocks = _pallas_blocks(sq, sk) if use else None
    where = _rows_split(q.shape[0]) if blocks is not None else None
    if where is None:
        return "xla", None
    rows = q.size // (sq * q.shape[-1])  # batch · heads
    # the interpreter is asked for by name, to run the kernels: whatever the length
    forced = interpret or 4 * rows * sq * sk >= _KERNEL_MIN_SCORE_BYTES
    if (qkv_heads is not None and (forced or sq >= _TILE_MIN_SEQ)
            and _qkv_tile_fits(sq, qkv_heads, q.shape[-1], q.dtype, interpret)):
        return "tile", _Launch(interpret, None, *where)
    if forced or (max(sq, sk) >= _KERNEL_MIN_SEQ
                  and min(blocks) >= _KERNEL_MIN_BLOCK):
        return "blockwise", _Launch(interpret, blocks, *where)
    return "xla", None


def _count_dispatch(kernels, grouped=False, selected=False, folded=False):
    """One count a traced call site: dispatch is decided at trace time, so
    after a step has compiled the two counters say which path every
    attention call of the program took (the third how many of them had
    fewer key/value heads than query heads, the fourth how many of the
    kernels' were given a selection, the fifth how many of the kernels'
    took the folded causal kernels)."""
    from .. import profiler

    if kernels:
        profiler.incr("attention_dispatch_pallas")
    else:
        profiler.incr("attention_dispatch_xla")
    if grouped:
        profiler.incr("attention_dispatch_grouped")
    if kernels and selected:
        profiler.incr("attention_dispatch_masked")
    if kernels and folded:
        profiler.incr("attention_dispatch_folded")


def _blockwise_folds(path, launch, causal, q, k, v, seq_axis, group, selected):
    """Does a call that ``_kernel_path`` sent down ``path`` take the folded
    kernels?"""
    return path == "blockwise" and _folds(
        causal, q.shape[seq_axis], k.shape[seq_axis], launch.blocks, q.shape[-1], v.shape[-1],
        q.dtype, group, selected)


def _head_group(q, k, head_axis):
    """Query heads a key/value head (1: plain multi-head attention)."""
    h, h_kv = q.shape[head_axis], k.shape[head_axis]
    if h % h_kv:
        raise ValueError(f"{h} query heads do not divide over {h_kv} "
                         f"key/value heads")
    return h // h_kv


def _repeat_heads(k, v, group, head_axis):
    """The XLA paths' grouped-query form: each key/value head repeated for
    its group's query heads (short sequences only reach them); the repeat's
    own transpose sums the group's dk and dv."""
    if group == 1:
        return k, v
    return (jnp.repeat(k, group, axis=head_axis),
            jnp.repeat(v, group, axis=head_axis))


def _blockwise_forward(q, k, v, select, causal, scale, launch, with_lse):
    """The forward kernel on [B, H, S, D] operands (``select`` [B, S, Sk] int8
    or None): the output, and with ``with_lse`` each query row's log-sum-exp
    beside it, float32 [B·H, S] (one lane of what the kernel writes
    lane-replicated)."""
    b, h, s, d = q.shape
    h_kv, sk, d_v = k.shape[1], k.shape[2], v.shape[-1]
    arrays = (q.reshape(b * h, s, d), k.reshape(b * h_kv, sk, d),
              v.reshape(b * h_kv, sk, d_v)) + (() if select is None else (select,))
    got = _on_mesh(
        launch, lambda q, k, v, select=None: _flash_fwd_pallas(
            q, k, v, causal, scale, launch.interpret, *launch.blocks,
            with_lse=with_lse, select=select), *arrays)
    if with_lse:
        return got[0].reshape(b, h, s, d_v), got[1][:, :, 0]
    return got.reshape(b, h, s, d_v)


def _blockwise_forward_named(q, k, v, select, causal, scale, launch):
    """What both forward rules share: the kernel's output and log-sum-exp
    under their names, and the backward's residuals.  The rules hand the
    NAMED arrays on as primal outputs too: a primal output taken from the
    kernel's own result would keep the kernel alive in a recomputed forward
    whose checkpoint saved the names."""
    from jax.ad_checkpoint import checkpoint_name

    out, lse = _blockwise_forward(q, k, v, select, causal, scale, launch, True)
    out, lse = checkpoint_name(out, KEEP_OUT), checkpoint_name(lse, KEEP_LSE)
    return out, lse, (q, k, v, out, lse, select)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _flash_kernels(q, k, v, causal, scale, launch, select=None):
    """[B, H, S, D] attention in the blockwise kernels, forward and backward;
    ``k`` and ``v`` [B, Hkv, Sk, ·] may have fewer heads (grouped-query: query
    head ``h`` reads key/value head ``h // (H / Hkv)``).  ``select`` [B, S,
    Sk] int8, if given, is a selection shared by a batch row's heads: a score
    is live only where it is non-zero (and, under ``causal``, visible); every
    query must select at least one key it can see.  It carries no gradient."""
    return _blockwise_forward(q, k, v, select, causal, scale, launch, False)


def _flash_kernels_fwd(q, k, v, causal, scale, launch, select=None):
    """VJP forward: also save (o, lse) so the backward runs blockwise
    without ever materializing S×S."""
    out, _, res = _blockwise_forward_named(q, k, v, select, causal, scale, launch)
    return out, res


def _flash_kernels_bwd(causal, scale, launch, res, do):
    q, k, v, o, lse, select = res
    b, h, s, d = q.shape
    h_kv, sk, d_v = k.shape[1], k.shape[2], v.shape[-1]
    arrays = (q.reshape(b * h, s, d), k.reshape(b * h_kv, sk, d),
              v.reshape(b * h_kv, sk, d_v), do.reshape(b * h, s, d_v),
              o.reshape(b * h, s, d_v), lse) + (() if select is None else (select,))
    dq, dk, dv = _on_mesh(
        launch, lambda *a: _flash_bwd_pallas(
            *a[:6], causal, scale, launch.interpret, *launch.blocks,
            select=a[6] if len(a) > 6 else None), *arrays)
    from .nn import _zero_cotangent

    return (dq.reshape(q.shape), dk.reshape(k.shape), dv.reshape(v.shape),
            None if select is None else _zero_cotangent(select))


_flash_kernels.defvjp(_flash_kernels_fwd, _flash_kernels_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _flash_kernels_lse(q, k, v, causal, scale, launch, select=None):
    """:func:`_flash_kernels` that also returns each query's log-sum-exp of
    its live scores, float32 [B, H, S] (what the forward kernel writes for
    the backward anyway): ``exp(score - lse)`` is the probability.  The
    log-sum-exp is a by-product and carries no gradient."""
    out, lse = _blockwise_forward(q, k, v, select, causal, scale, launch, True)
    return out, lse.reshape(q.shape[:3])


def _flash_kernels_lse_fwd(q, k, v, causal, scale, launch, select=None):
    out, lse, res = _blockwise_forward_named(q, k, v, select, causal, scale, launch)
    return (out, lse.reshape(q.shape[:3])), res


def _flash_kernels_lse_bwd(causal, scale, launch, res, cts):
    return _flash_kernels_bwd(causal, scale, launch, res, cts[0])


_flash_kernels_lse.defvjp(_flash_kernels_lse_fwd, _flash_kernels_lse_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _flash_xla(q, k, v, causal, scale):
    return attention_reference(q, k, v, causal, scale)


def _flash_xla_fwd(q, k, v, causal, scale):
    return attention_reference(q, k, v, causal, scale), (q, k, v)


def _flash_bwd_xla(causal, scale, res, do):
    """Rematerialized backward (standard flash-attention gradient algebra);
    XLA fallback — materializes S×S, fine at short sequence lengths.
    bf16 operands / fp32 accumulation, same rationale as
    :func:`attention_reference`."""
    q, k, v = res
    prec = (jax.lax.Precision.HIGHEST if q.dtype == jnp.float32
            else jax.lax.Precision.DEFAULT)
    mm = functools.partial(jnp.einsum, preferred_element_type=jnp.float32,
                           precision=prec)
    s = mm("...qd,...kd->...qk", q, k) * scale
    if causal:
        sq, sk = s.shape[-2], s.shape[-1]
        mask = jnp.arange(sq)[:, None] >= jnp.arange(sk)[None, :]
        s = jnp.where(mask, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)                   # fp32 [.., Sq, Sk]
    pc = p.astype(v.dtype)
    o = mm("...qk,...kd->...qd", pc, v)              # fp32 accum
    dv = mm("...qk,...qd->...kd", pc, do)
    dp = mm("...qd,...kd->...qk", do, v)
    delta = jnp.sum(do.astype(jnp.float32) * o, axis=-1, keepdims=True)
    ds = (p * (dp - delta)).astype(q.dtype)
    dq = mm("...qk,...kd->...qd", ds, k) * scale
    dk = mm("...qk,...qd->...kd", ds, q) * scale
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


_flash_xla.defvjp(_flash_xla_fwd, _flash_bwd_xla)


def _as_selection(select):
    """The kernels' form of a selection: int8, non-zero = chosen."""
    return None if select is None else (select != 0).astype(jnp.int8)


def flash_attention(q, k, v, causal=False, scale=None, select=None):
    """Fused attention on [B, H, S, D] arrays; differentiable; bf16-safe.
    ``k`` and ``v`` may have fewer heads, [B, Hkv, Sk, ·] with Hkv dividing H
    (grouped-query attention).  ``select`` [B, Sq, Sk] (bool or integer,
    non-zero = chosen) is a selection of keys a query, shared by the heads:
    a score is live only where the key is chosen and, under ``causal``,
    visible; every query must choose a key it can see.  No gradient reaches
    it.  Without one the call is what it always was."""
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    group = _head_group(q, k, 1)
    path, launch = _kernel_path(q, k)
    _count_dispatch(path != "xla", group > 1, select is not None,
                    _blockwise_folds(path, launch, causal, q, k, v, 2, group, select is not None))
    if path == "blockwise":
        return _flash_kernels(q, k, v, causal, float(scale), launch,
                              _as_selection(select))
    k, v = _repeat_heads(k, v, group, 1)
    if select is not None:
        return _selected_xla(attention_reference, q, k, v, causal, float(scale), select)
    return _flash_xla(q, k, v, causal, float(scale))


def _selected_xla(reference, q, k, v, causal, scale, select):
    """The XLA path under a selection: the plain expression, its backward
    rematerialized (``jax.checkpoint``) as the paths' own rules do."""
    select = lax.stop_gradient(_as_selection(select))
    return jax.checkpoint(
        lambda q, k, v, select: reference(q, k, v, causal, scale, select))(q, k, v, select)


# ---------------------------------------------------------------------------
# [B, S, H, Dh] layout path — no materialized head transposes (short-seq XLA
# tier; the layout shuffles live inside the dot_generals where the MXU's
# layout assignment absorbs them)
# ---------------------------------------------------------------------------


def _causal_mask(s):
    sq, sk = s.shape[-2], s.shape[-1]
    mask = jnp.arange(sq)[:, None] >= jnp.arange(sk)[None, :]
    return jnp.where(mask, s, -jnp.inf)


def attention_reference_bshd(q, k, v, causal=False, scale=None, select=None):
    """Plain jnp attention over [B, S, H, Dh] operands (head axis stays in
    place; same fp32-accumulate / fp32-softmax policy as
    :func:`attention_reference`, and its ``select``)."""
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    prec = (jax.lax.Precision.HIGHEST if q.dtype == jnp.float32
            else jax.lax.Precision.DEFAULT)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   preferred_element_type=jnp.float32, precision=prec) * scale
    p = jax.nn.softmax(_live(s, causal, select), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v,
                      preferred_element_type=jnp.float32,
                      precision=prec).astype(v.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _flash_bshd(q, k, v, causal, scale):
    return attention_reference_bshd(q, k, v, causal, scale)


def _flash_bshd_fwd(q, k, v, causal, scale):
    return attention_reference_bshd(q, k, v, causal, scale), (q, k, v)


def _flash_bshd_bwd(causal, scale, res, do):
    """bshd attention backward: rematerialize scores and softmax, the bshd
    twin of :func:`_flash_bwd_xla`.  (Saving bf16 probabilities instead
    LOST ~3 % end to end on BERT-base B=64 S=128: the saved tensor's
    write and read broke XLA's fusion of the recompute into the backward
    matmuls, docs/PERF_NOTES.md.)"""
    q, k, v = res
    prec = (jax.lax.Precision.HIGHEST if q.dtype == jnp.float32
            else jax.lax.Precision.DEFAULT)
    mm = functools.partial(jnp.einsum, preferred_element_type=jnp.float32,
                           precision=prec)
    s = mm("bqhd,bkhd->bhqk", q, k) * scale
    if causal:
        s = _causal_mask(s)
    p = jax.nn.softmax(s, axis=-1)                   # fp32 [B, H, Sq, Sk]
    pc = p.astype(v.dtype)
    dv = mm("bhqk,bqhd->bkhd", pc, do)
    dp = mm("bqhd,bkhd->bhqk", do, v)
    # delta_q = Σ_k dp∘p  (== Σ_d do∘o, the flash identity — saves o)
    delta = jnp.sum(dp * p, axis=-1, keepdims=True)
    ds = (p * (dp - delta)).astype(q.dtype)
    dq = mm("bhqk,bkhd->bqhd", ds, k) * scale
    dk = mm("bhqk,bqhd->bkhd", ds, q) * scale
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


_flash_bshd.defvjp(_flash_bshd_fwd, _flash_bshd_bwd)


def _attend_bshd(q, k, v, causal, scale, select=None, with_lse=False):
    """Dispatch [B, S, H, Dh] attention: the bshd XLA path, or transpose +
    the blockwise kernels where ``_kernel_path`` says they win (the two
    transposes are in the measurements it rests on).  Traced under the
    ``attn.core`` scope, which separates attention from the projections
    round it in a device trace.  ``select``: :func:`flash_attention`'s.
    ``with_lse`` returns ``(out, lse, launch)``: the kernels' log-sum-exp
    [B, H, S] and how they were launched, both None on the XLA path."""
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    group = _head_group(q, k, 2)
    path, launch = _kernel_path(q, k, seq_axis=1)
    _count_dispatch(path != "xla", group > 1, select is not None,
                    _blockwise_folds(path, launch, causal, q, k, v, 1, group, select is not None))
    lse = None
    with jax.named_scope("attn.core"):
        if path == "blockwise":
            t = lambda x: x.transpose(0, 2, 1, 3)
            operands = (t(q), t(k), t(v), causal, float(scale), launch, _as_selection(select))
            if with_lse:
                out, lse = _flash_kernels_lse(*operands)
            else:
                out = _flash_kernels(*operands)
            out = out.transpose(0, 2, 1, 3)
        else:
            k, v = _repeat_heads(k, v, group, 2)
            if select is not None:
                out = _selected_xla(attention_reference_bshd, q, k, v, causal,
                                    float(scale), select)
            else:
                out = _flash_bshd(q, k, v, causal, float(scale))
    return (out, lse, launch) if with_lse else out


from .registry import register  # noqa: E402


@register("fused_attention")
def fused_attention(q, k, v, num_heads=1, causal=False, scale=None,
                    kv_heads=None):
    """[B, S, D] convenience form: split heads → flash attention → merge
    (``v`` [B, S, H·Dv] with its own head size).  Registered so it is reachable as ``nd.fused_attention`` /
    ``nd.contrib.fused_attention`` (the role cuDNN fused MHA plays for the
    reference's GPU builds).  ``kv_heads`` (default ``num_heads``) is the
    number of key/value heads of grouped-query attention: ``k`` [B, S,
    kv_heads·Dh] and ``v`` [B, S, kv_heads·Dv], query head ``h`` reading
    key/value head ``h // (num_heads / kv_heads)``."""
    b, s, d = q.shape
    h = num_heads
    h_kv = h if kv_heads is None else int(kv_heads)
    if (d % h or v.shape[-1] % h_kv or h % h_kv
            or k.shape[-1] * h != d * h_kv):
        raise ValueError(f"feature dims {d}/{k.shape[-1]}/{v.shape[-1]} do "
                         f"not split into num_heads {h}, kv_heads {h_kv}")

    def split(x, heads):
        return x.reshape(b, x.shape[1], heads, x.shape[-1] // heads)

    # v may be narrower or wider a head than q and k (latent attention:
    # 192-wide queries and keys, 128-wide values); the output has v's width
    out = _attend_bshd(split(q, h), split(k, h_kv), split(v, h_kv), causal, scale)
    return out.reshape(b, s, h * (v.shape[-1] // h_kv))


@register("fused_qkv_attention")
def fused_qkv_attention(qkv, num_heads=1, causal=False, scale=None):
    """Self-attention straight from the fused QKV projection output
    [B, S, 3·D]: the q/k/v split AND the head split are one free reshape
    ([B, S, 3, H, Dh] decomposes the projection's output columns exactly),
    and neither the one-tile kernels (which read the projection in place)
    nor the bshd XLA core materializes a head transpose."""
    b, s, d3 = qkv.shape
    h = num_heads
    d = d3 // 3
    if d % h or d3 % 3:
        raise ValueError(f"qkv dim {d3} not divisible into 3 heads×{h}")
    if scale is None:
        scale = 1.0 / math.sqrt(d // h)
    x = qkv.reshape(b, s, 3, h, d // h)
    path, launch = _kernel_path(x[:, :, 0], x[:, :, 1], seq_axis=1, qkv_heads=h)
    if path == "tile":
        # the whole S×S tile in VMEM, the projection read where it lies
        _count_dispatch(True)
        with jax.named_scope("attn.core"):
            return _flash_qkv_tile(qkv, h, causal, float(scale), launch)
    out = _attend_bshd(x[:, :, 0], x[:, :, 1], x[:, :, 2], causal, scale)
    return out.reshape(b, s, d)


@register("fused_kv_attention")
def fused_kv_attention(q, kv, num_heads=1, causal=False, scale=None):
    """Cross-attention twin of :func:`fused_qkv_attention`: q [B, Sq, D]
    from the decoder, kv [B, Sk, 2·D] from the fused KV projection of the
    encoder memory."""
    b, sq, d = q.shape
    h = num_heads
    if d % h or kv.shape[-1] != 2 * d:
        raise ValueError(f"kv dim {kv.shape[-1]} must be 2×{d}, heads {h}")
    dh = d // h
    x = kv.reshape(b, kv.shape[1], 2, h, dh)
    out = _attend_bshd(q.reshape(b, sq, h, dh), x[:, :, 0], x[:, :, 1],
                       causal, scale)
    return out.reshape(b, sq, d)


# ---------------------------------------------------------------------------
# interleaved_matmul_* (parity: [U:src/operator/contrib/transformer.cc], the
# GluonNLP 0.x fused-MHA fast path).  Layout convention: projections are
# [S, B, H·3·Dh] (self-attn, q/k/v interleaved PER HEAD) or [S, B, H·2·Dh]
# (enc-dec k/v).  On TPU these are einsum forms — XLA's layout assignment
# does what the reference's hand-written interleaved GEMMs do by hand.
# ---------------------------------------------------------------------------


def _deinterleave(proj, heads, parts):
    s, b, hpd = proj.shape
    if hpd % (heads * parts):
        raise ValueError(
            f"interleaved projection width {hpd} is not divisible by "
            f"heads({heads})×{parts}")
    dh = hpd // (heads * parts)
    x = proj.reshape(s, b, heads, parts, dh)
    return tuple(x[:, :, :, i] for i in range(parts))  # each [S, B, H, Dh]


@register("_contrib_interleaved_matmul_selfatt_qk")
def interleaved_matmul_selfatt_qk(queries_keys_values, heads=1):
    """scores[B·H, Sq, Sk] = Q·Kᵀ/√Dh from the interleaved projection."""
    q, k, _ = _deinterleave(queries_keys_values, heads, 3)
    scale = 1.0 / math.sqrt(q.shape[-1])
    s = jnp.einsum("qbhd,kbhd->bhqk", q.astype(jnp.float32) * scale,
                   k.astype(jnp.float32))
    b, h, sq, sk = s.shape
    return s.reshape(b * h, sq, sk).astype(queries_keys_values.dtype)


@register("_contrib_interleaved_matmul_selfatt_valatt")
def interleaved_matmul_selfatt_valatt(queries_keys_values, attention, heads=1):
    """context [S, B, H·Dh] = attention · V with V from the interleaved
    projection; attention is [B·H, Sq, Sk]."""
    _, _, v = _deinterleave(queries_keys_values, heads, 3)  # [Sk, B, H, Dh]
    sk, b, h, dh = v.shape
    att = attention.reshape(b, h, -1, sk)
    out = jnp.einsum("bhqk,kbhd->qbhd", att.astype(jnp.float32),
                     v.astype(jnp.float32))
    sq = out.shape[0]
    return out.reshape(sq, b, h * dh).astype(queries_keys_values.dtype)


@register("_contrib_interleaved_matmul_encdec_qk")
def interleaved_matmul_encdec_qk(queries, keys_values, heads=1):
    """Cross-attention scores from separate Q [Sq, B, H·Dh] and interleaved
    KV [Sk, B, H·2·Dh]."""
    sq, b, hd = queries.shape
    if hd % heads:
        raise ValueError(f"query width {hd} not divisible by heads({heads})")
    dh = hd // heads
    q = queries.reshape(sq, b, heads, dh)
    k, _ = _deinterleave(keys_values, heads, 2)
    scale = 1.0 / math.sqrt(dh)
    s = jnp.einsum("qbhd,kbhd->bhqk", q.astype(jnp.float32) * scale,
                   k.astype(jnp.float32))
    return s.reshape(b * heads, sq, -1).astype(queries.dtype)


@register("_contrib_interleaved_matmul_encdec_valatt")
def interleaved_matmul_encdec_valatt(keys_values, attention, heads=1):
    _, v = _deinterleave(keys_values, heads, 2)  # [Sk, B, H, Dh]
    sk, b, h, dh = v.shape
    att = attention.reshape(b, h, -1, sk)
    out = jnp.einsum("bhqk,kbhd->qbhd", att.astype(jnp.float32),
                     v.astype(jnp.float32))
    return out.reshape(out.shape[0], b, h * dh).astype(keys_values.dtype)


# ---------------------------------------------------------------------------
# Latent attention (DeepSeek-V2/V3 "MLA") with rotary positions under YaRN
# ---------------------------------------------------------------------------


def yarn_mscale(factor, mscale):
    """YaRN's attention temperature, ``0.1 · mscale · ln(factor) + 1``."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_rotary_tables(seq, dim, theta=10000.0, factor=1.0, original=4096,
                       beta_fast=32.0, beta_slow=1.0, mscale=1.0,
                       mscale_all_dim=0.0):
    """``(cos, sin)``, each float32 NumPy ``[seq, dim/2]``, for rotary
    positions 0..seq-1 with the YaRN frequency blend of the DeepSeek-V3
    modeling file: frequencies that turn more than ``beta_fast`` times in
    the ``original`` context keep their base value, those that turn less
    than ``beta_slow`` times are divided by ``factor``, a linear ramp lies
    between.  ``factor`` 1 is plain RoPE.  Built at trace time: a constant
    of the compiled program."""
    import numpy as np

    half = dim // 2
    base = theta ** (np.arange(half, dtype=np.float64) * 2.0 / dim)
    inv_freq = 1.0 / base
    scale = 1.0
    if factor > 1:
        def correction_dim(rotations):
            return (dim * math.log(original / (rotations * 2 * math.pi))
                    / (2 * math.log(theta)))
        low = max(math.floor(correction_dim(beta_fast)), 0)
        high = min(math.ceil(correction_dim(beta_slow)), dim - 1)
        ramp = np.clip((np.arange(half, dtype=np.float64) - low)
                       / max(high - low, 1e-3), 0.0, 1.0)
        keep = 1.0 - ramp                     # 1: base frequency kept
        inv_freq = inv_freq / factor * (1.0 - keep) + inv_freq * keep
        scale = (yarn_mscale(factor, mscale)
                 / yarn_mscale(factor, mscale_all_dim))
    angles = np.arange(seq, dtype=np.float64)[:, None] * inv_freq[None, :]
    return ((np.cos(angles) * scale).astype(np.float32),
            (np.sin(angles) * scale).astype(np.float32))


def rotary_stream_of_pair(half, sections=None):
    """Which position stream each of the ``half`` frequency pairs reads:
    pair ``i`` reads stream ``j`` where ``i`` falls in the ``j``-th of
    ``sections`` (contiguous, summing to ``half``: ``[16, 24, 24]`` gives pairs
    0..15 to stream 0, 16..39 to stream 1, 40..63 to stream 2).  None: one
    stream."""
    import numpy as np

    if sections is None:
        return np.zeros((half,), np.int32)
    if sum(sections) != half:
        raise ValueError(f"rotary sections {list(sections)} do not add up to "
                         f"the {half} frequency pairs")
    return np.repeat(np.arange(len(sections), dtype=np.int32), list(sections))


def multi_stream_rotary_tables(positions, dim, theta=10000.0, sections=None):
    """``(cos, sin)``, each float32 ``[B, S, dim/2]``, for rotary positions
    given as streams ``positions`` [streams, B, S] (text: every stream is the
    token's index; an image patch reads its time, row and column from three):
    frequency pair ``i`` turns by ``positions[stream_of_pair[i]] · theta^(-2i/
    dim)``.  Traced: the positions are data."""
    half = dim // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / dim)
    stream = rotary_stream_of_pair(half, sections)
    pos = jnp.moveaxis(positions.astype(jnp.float32), 0, -1)[..., stream]   # [B, S, half]
    angles = pos * inv_freq
    return jnp.cos(angles), jnp.sin(angles)


def apply_rotary(x, cos, sin, pairing="interleaved"):
    """Rotate pairs of the last axis of ``x`` [B, S, H, dim] by the
    position's angles.  ``cos`` / ``sin`` are ``[S, dim/2]`` (one position
    stream, the same for every batch row: :func:`yarn_rotary_tables`) or ``[B,
    S, dim/2]`` (:func:`multi_stream_rotary_tables`).  ``pairing``
    ``"interleaved"`` rotates ``(x[2i], x[2i+1])`` and lays the result out as
    the DeepSeek-V3 modeling file leaves it (first halves, then second
    halves), which a dot product of two rotated vectors does not see;
    ``"half"`` rotates ``(x[i], x[i + dim/2])`` in place (rotate-half)."""
    x32 = x.astype(jnp.float32)
    if pairing == "interleaved":
        pairs = x32.reshape(x.shape[:-1] + (x.shape[-1] // 2, 2))
        a, b = pairs[..., 0], pairs[..., 1]
    elif pairing == "half":
        a, b = jnp.split(x32, 2, axis=-1)
    else:
        raise ValueError(f"pairing {pairing!r}: 'interleaved' or 'half'")
    lead = (None,) * (3 - cos.ndim)        # [S, half] → [1, S, half]
    c, s = cos[lead + (Ellipsis, None, slice(None))], sin[lead + (Ellipsis, None, slice(None))]
    return jnp.concatenate([a * c - b * s, b * c + a * s], -1).astype(x.dtype)


@register("rotary_embedding")
def rotary_embedding(data, theta=10000.0):
    """Rotary positions ``0 .. S−1`` on every dim of ``data`` [B, S, H, dim]
    in the rotate-half form (:func:`apply_rotary`'s ``"half"``), frequencies
    ``theta^(−2i/dim)``: float32 inside, stored in the input's dtype."""
    cos, sin = yarn_rotary_tables(data.shape[1], data.shape[-1], theta)
    return apply_rotary(data, cos, sin, "half")


@register("latent_attention")
def latent_attention(x, w_qa, g_q, w_qb, w_kva, g_kv, w_kvb, w_o,
                     num_heads=1, qk_nope_dim=128, qk_rope_dim=64, v_dim=128,
                     eps=1e-6, causal=True, rope_theta=10000.0,
                     yarn_factor=1.0, yarn_original=4096, yarn_beta_fast=32.0,
                     yarn_beta_slow=1.0, yarn_mscale_=1.0,
                     yarn_mscale_all_dim=0.0, scope="latent_attention"):
    """Multi-head latent self-attention on ``x`` [B, S, d] (already normed).

    ``c_q = RMSNorm(W_qa x)``; ``[q_nope | q_rope] = W_qb c_q`` per head;
    ``[c_kv | k_rope] = W_kva x`` with ``k_rope`` shared by all heads;
    ``[k_nope | v] = W_kvb RMSNorm(c_kv)`` per head; rotary (YaRN) on the
    rope parts; softmax over ``(q·k) · (nope+rope)^-0.5 · mscale²``; ``W_o``.
    Weights are ``[out, in]``; no bias.  Queries and keys are
    ``qk_nope_dim + qk_rope_dim`` wide, values ``v_dim``: the core goes
    through the attention dispatcher (``_attend_bshd``) as it is, values
    unpadded.  ``scope`` names the ``jax.named_scope`` of the whole op and,
    with ``.core``, of the score/softmax/value part."""
    from .nn import rms_norm

    b, s, _ = x.shape
    h, dn, dr = int(num_heads), int(qk_nope_dim), int(qk_rope_dim)
    kv_rank = w_kva.shape[0] - dr
    prec = (jax.lax.Precision.HIGHEST if x.dtype == jnp.float32
            else jax.lax.Precision.DEFAULT)

    def proj(a, w):
        return jnp.einsum("...i,oi->...o", a, w.astype(a.dtype),
                          precision=prec)

    with jax.named_scope(scope):
        q = proj(rms_norm(proj(x, w_qa), g_q, eps=eps), w_qb)
        q = q.reshape(b, s, h, dn + dr)
        kva = proj(x, w_kva)
        c_kv, k_rope = kva[..., :kv_rank], kva[..., kv_rank:]
        kv = proj(rms_norm(c_kv, g_kv, eps=eps), w_kvb)
        kv = kv.reshape(b, s, h, dn + int(v_dim))
        cos, sin = yarn_rotary_tables(
            s, dr, rope_theta, yarn_factor, yarn_original, yarn_beta_fast,
            yarn_beta_slow, yarn_mscale_, yarn_mscale_all_dim)
        q_rope = apply_rotary(q[..., dn:], cos, sin)
        k_rope = apply_rotary(k_rope[:, :, None, :], cos, sin)
        qf = jnp.concatenate([q[..., :dn], q_rope], -1)
        kf = jnp.concatenate(
            [kv[..., :dn], jnp.broadcast_to(k_rope, (b, s, h, dr))], -1)
        scale = ((dn + dr) ** -0.5
                 * yarn_mscale(yarn_factor, yarn_mscale_all_dim) ** 2)
        with jax.named_scope(scope + ".core"):
            out = _attend_bshd(qf, kf, kv[..., dn:], causal, scale)
        return proj(out.reshape(b, s, h * int(v_dim)), w_o)
