"""Fused scaled-dot-product attention — the TPU answer to cuDNN fused
attention (the reference has no fused attention at all; its transformer
support lived out-of-repo in GluonNLP.  SURVEY.md §5 marks this as the one
area where this framework intentionally EXCEEDS the reference).

Three tiers, chosen by :func:`flash_attention`:

1. **Pallas flash kernel** (compiled by Mosaic on TPU; the Pallas
   interpreter only when asked for by name, ``MXNET_TPU_FLASH=interpret``
   — the CPU test tier does): blockwise online-softmax forward — queries tiled over the grid, K/V
   streamed through VMEM in ``block_k`` chunks, so the S×S score matrix is
   never materialized in HBM.  Accumulation in fp32 on the MXU
   (``preferred_element_type``), inputs may be bf16.
2. **XLA reference path** (non-TPU backends / ``MXNET_TPU_FLASH=off``):
   same math as one fused jnp expression; XLA fuses adequately for short
   sequences.
3. **Ring attention** (``parallel/ring.py``) for sequence-parallel long
   context — built on the same online-softmax update.

Gradients: ``jax.custom_vjp`` — backward recomputes attention probabilities
from the saved (q, k, v), so no S×S residual is stored *between* fwd and
bwd.  The backward is seq-length gated (thresholds below): short sequences
take a rematerialized XLA backward (one fused S×S program — faster when
S×S fits comfortably), long sequences take the two-pass blockwise Pallas
backward (`_flash_bwd_pallas`) whose memory stays linear in S.

Layout: :func:`fused_qkv_attention` / :func:`fused_kv_attention` keep the
``[B, S, H, Dh]`` layout end-to-end on the short-sequence XLA path so the
head split/merge is a free reshape of the QKV matmul output and XLA folds
the remaining dimension shuffles into the attention dot_generals — no
materialized head transposes (docs/PERF_NOTES.md round-3 win).  The Pallas
kernels want ``[B·H, S, Dh]`` physically, so the long-context path pays
the two transposes (negligible against O(S²) attention work there).
"""
from __future__ import annotations

import functools
import math
import os

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as _pl
from jax.experimental.pallas import tpu as _pltpu

__all__ = ["flash_attention", "attention_reference", "latent_attention",
           "yarn_rotary_tables", "apply_rotary"]

# The kernels keep one head's whole K and V (backward: Q, dO, O and the
# log-sum-exp) in VMEM beside their 512-wide tiles: 16.5 MB at S 4096 with
# 192-wide keys, over the compiler's default scoped limit of 16 MiB by half a
# megabyte in some programs and not in others (where XLA places an operand
# decides); a v5e core has 128 MiB.
_MOSAIC_PARAMS = _pltpu.CompilerParams(vmem_limit_bytes=48 << 20)

# TPU lane width: row statistics (lse) are replicated across a 128-lane
# trailing dim so their blocks satisfy Mosaic's (8, 128) tiling rule.
_LANE = 128


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


# ---------------------------------------------------------------------------
# Pallas forward kernel
# ---------------------------------------------------------------------------


def online_softmax_update(o, m, l, s, v, matmul):
    """One blockwise online-softmax accumulation step (shared by the Pallas
    kernel below and parallel/ring.py).  ``m``/``l`` carry a trailing
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


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, block_k, causal, scale):
    """One (batch·head, q-block) grid cell: stream K/V blocks, online
    softmax in fp32.  Shapes: q_ref [1, Bq, D], k_ref [1, Sk, D],
    v_ref [1, Sk, Dv] (Dv may differ from D: latent attention has 192-wide
    queries and keys and 128-wide values).

    Operands stay in their input dtype (bf16 rides the MXU at full rate)
    with fp32 accumulation via preferred_element_type; matmul precision is
    pinned per-dtype because the package-global 'highest' default would
    request an fp32 contraction on bf16 operands, which Mosaic rejects."""
    i = _pl.program_id(1)
    block_q = q_ref.shape[1]
    seq_k = k_ref.shape[1]
    nk = seq_k // block_k
    prec = (jax.lax.Precision.HIGHEST if q_ref.dtype == jnp.float32
            else jax.lax.Precision.DEFAULT)  # bf16 AND fp16 operands

    q = q_ref[0]  # [Bq, D], native dtype
    m0 = jnp.full((block_q, 1), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((block_q, 1), jnp.float32)
    acc0 = jnp.zeros((block_q, v_ref.shape[2]), jnp.float32)

    def body(j, carry):
        m, l, acc = carry
        k = k_ref[0, _pl.ds(j * block_k, block_k), :]
        v = v_ref[0, _pl.ds(j * block_k, block_k), :]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
            precision=prec,
        ) * scale  # [Bq, Bk], fp32 accumulate then scale
        if causal:
            q_pos = i * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
            k_pos = j * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
            s = jnp.where(q_pos >= k_pos, s, -jnp.inf)
        acc_new, m_new, l_new = online_softmax_update(
            acc, m, l, s, v,
            lambda p, v: jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32, precision=prec,
            ),
        )
        return m_new, l_new, acc_new

    if causal:
        # Skip K/V blocks entirely in the masked future: q-block i only
        # attends to k positions < (i+1)*block_q (halves FLOPs/bandwidth
        # for decoder self-attention vs. streaming all nk blocks).
        nk_bound = jnp.minimum(nk, ((i + 1) * block_q + block_k - 1) // block_k)
    else:
        nk_bound = nk
    m, l, acc = lax.fori_loop(0, nk_bound, body, (m0, l0, acc0))
    l = jnp.where(l == 0.0, 1.0, l)
    o_ref[0] = (acc / l).astype(o_ref.dtype)
    if lse_ref is not None:
        # log-sum-exp per query row, saved for the blockwise backward:
        # p = exp(s - lse) reproduces softmax without re-running the
        # online rescaling.  Replicated across a 128-lane trailing dim to
        # satisfy TPU tiling (same layout as jax's reference TPU kernel).
        # Fully-masked rows get lse = 0 (m_safe), so exp(-inf - 0) = 0
        # keeps their gradient contributions zero.
        m_safe = jnp.where(jnp.isneginf(m), 0.0, m)
        lse_ref[0] = jnp.broadcast_to(m_safe + jnp.log(l), lse_ref.shape[1:])


def _flash_fwd_pallas(q, k, v, causal, scale, interpret, block_q=128, block_k=128,
                      with_lse=False):
    """q/k: [BH, S, D], v: [BH, S, Dv] (batch·heads flattened).
    ``with_lse=True`` also returns the per-row log-sum-exp [BH, S] for the
    blockwise backward."""
    bh, sq, d = q.shape
    sk, dv = k.shape[1], v.shape[2]
    o_shape = (bh, sq, dv)
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    if sq % block_q or sk % block_k:
        raise ValueError(f"sequence lengths ({sq},{sk}) must divide blocks ({block_q},{block_k})")
    grid = (bh, sq // block_q)
    if with_lse:
        kernel = functools.partial(_fwd_kernel, block_k=block_k, causal=causal, scale=scale)
        out_shape = (jax.ShapeDtypeStruct(o_shape, q.dtype),
                     jax.ShapeDtypeStruct((bh, sq, _LANE), jnp.float32))
        out_specs = (_pl.BlockSpec((1, block_q, dv), lambda b, i: (b, i, 0)),
                     _pl.BlockSpec((1, block_q, _LANE), lambda b, i: (b, i, 0)))
    else:
        def kernel(q_ref, k_ref, v_ref, o_ref, **_):
            _fwd_kernel(q_ref, k_ref, v_ref, o_ref, None,
                        block_k=block_k, causal=causal, scale=scale)
        out_shape = jax.ShapeDtypeStruct(o_shape, q.dtype)
        out_specs = _pl.BlockSpec((1, block_q, dv), lambda b, i: (b, i, 0))
    return _pl.pallas_call(
        kernel,
        out_shape=out_shape,
        grid=grid,
        in_specs=[
            _pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0)),
            _pl.BlockSpec((1, sk, d), lambda b, i: (b, 0, 0)),
            _pl.BlockSpec((1, sk, dv), lambda b, i: (b, 0, 0)),
        ],
        out_specs=out_specs,
        interpret=interpret,
        compiler_params=_MOSAIC_PARAMS,
    )(q, k, v)


# ---------------------------------------------------------------------------
# Pallas backward kernels (standard two-pass flash gradient: a dq pass
# gridded over q blocks and a dk/dv pass gridded over k blocks, both
# streaming the opposite operand — the S×S score matrix never exists in HBM)
# ---------------------------------------------------------------------------


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref, dq_ref, *,
                   block_k, causal, scale):
    i = _pl.program_id(1)
    block_q, d = q_ref.shape[1], q_ref.shape[2]
    seq_k = k_ref.shape[1]
    nk = seq_k // block_k
    prec = (jax.lax.Precision.HIGHEST if q_ref.dtype == jnp.float32
            else jax.lax.Precision.DEFAULT)

    q = q_ref[0]                       # [Bq, D] native dtype
    do = do_ref[0]                     # [Bq, D]
    lse = lse_ref[0][:, :1]            # [Bq, 1] fp32 (lane-replicated buffer)
    # delta = rowsum(do ⊙ o): cheap elementwise reduce done in-kernel so no
    # extra HBM buffer/pass is needed
    delta = jnp.sum(do.astype(jnp.float32) * o_ref[0].astype(jnp.float32),
                    axis=-1, keepdims=True)

    def body(j, acc):
        k = k_ref[0, _pl.ds(j * block_k, block_k), :]
        v = v_ref[0, _pl.ds(j * block_k, block_k), :]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
            precision=prec) * scale
        if causal:
            q_pos = i * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
            k_pos = j * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
            s = jnp.where(q_pos >= k_pos, s, -jnp.inf)
        p = jnp.exp(s - lse)           # [Bq, Bk]; masked → exp(-inf) = 0
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
            precision=prec)
        ds = (p * (dp - delta)).astype(k.dtype)
        return acc + jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32,
            precision=prec)

    if causal:
        nk_bound = jnp.minimum(nk, ((i + 1) * block_q + block_k - 1) // block_k)
    else:
        nk_bound = nk
    acc = lax.fori_loop(0, nk_bound, body,
                        jnp.zeros((block_q, d), jnp.float32))
    dq_ref[0] = (acc * scale).astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref,
                    dk_ref, dv_ref, *, block_q, causal, scale):
    i = _pl.program_id(1)
    block_k, d, d_v = k_ref.shape[1], k_ref.shape[2], v_ref.shape[2]
    seq_q = q_ref.shape[1]
    nq = seq_q // block_q
    prec = (jax.lax.Precision.HIGHEST if q_ref.dtype == jnp.float32
            else jax.lax.Precision.DEFAULT)

    k = k_ref[0]                       # [Bk, D]
    v = v_ref[0]                       # [Bk, D]

    def body(j, carry):
        dk, dv = carry
        q = q_ref[0, _pl.ds(j * block_q, block_q), :]
        do = do_ref[0, _pl.ds(j * block_q, block_q), :]
        lse = lse_ref[0, _pl.ds(j * block_q, block_q), :1]
        delta = jnp.sum(
            do.astype(jnp.float32)
            * o_ref[0, _pl.ds(j * block_q, block_q), :].astype(jnp.float32),
            axis=-1, keepdims=True)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
            precision=prec) * scale    # [Bq, Bk]
        if causal:
            q_pos = j * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
            k_pos = i * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
            s = jnp.where(q_pos >= k_pos, s, -jnp.inf)
        p = jnp.exp(s - lse)
        dv = dv + jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=prec)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
            precision=prec)
        ds = (p * (dp - delta)).astype(q.dtype)
        dk = dk + jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32,
            precision=prec)
        return dk, dv

    j0 = (i * block_k) // block_q if causal else 0
    dk, dv = lax.fori_loop(
        j0, nq, body,
        (jnp.zeros((block_k, d), jnp.float32), jnp.zeros((block_k, d_v), jnp.float32)))
    dk_ref[0] = (dk * scale).astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


def _flash_bwd_pallas(q, k, v, do, o, lse, causal, scale, interpret,
                      block_q=128, block_k=128):
    """q/k: [BH, S, D]; v/do/o: [BH, S, Dv]; lse: [BH, Sq, _LANE] fp32
    → (dq, dk, dv)."""
    bh, sq, d = q.shape
    sk, d_v = k.shape[1], v.shape[2]
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    grid_q = (bh, sq // block_q)
    grid_k = (bh, sk // block_k)

    full = lambda s, w: _pl.BlockSpec((1, s, w), lambda b, i: (b, 0, 0))
    blk = lambda rows, w: _pl.BlockSpec((1, rows, w), lambda b, i: (b, i, 0))

    dq = _pl.pallas_call(
        functools.partial(_bwd_dq_kernel, block_k=block_k, causal=causal, scale=scale),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        grid=grid_q,
        in_specs=[
            blk(block_q, d),                                          # q
            full(sk, d),                                              # k
            full(sk, d_v),                                            # v
            blk(block_q, d_v),                                        # do
            blk(block_q, d_v),                                        # o
            blk(block_q, _LANE),                                      # lse
        ],
        out_specs=blk(block_q, d),
        interpret=interpret,
        compiler_params=_MOSAIC_PARAMS,
    )(q, k, v, do, o, lse)

    dk, dv = _pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, block_q=block_q, causal=causal, scale=scale),
        out_shape=(jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)),
        grid=grid_k,
        in_specs=[
            full(sq, d),                                              # q
            blk(block_k, d),                                          # k
            blk(block_k, d_v),                                        # v
            full(sq, d_v),                                            # do
            full(sq, d_v),                                            # o
            full(sq, _LANE),                                          # lse
        ],
        out_specs=(blk(block_k, d), blk(block_k, d_v)),
        interpret=interpret,
        compiler_params=_MOSAIC_PARAMS,
    )(q, k, v, do, o, lse)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# Reference path (XLA-fused) + custom VJP
# ---------------------------------------------------------------------------


def attention_reference(q, k, v, causal=False, scale=None):
    """Plain jnp attention: q/k/v [B, H, S, D] (or [BH, S, D]).

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
    if causal:
        sq, sk = s.shape[-2], s.shape[-1]
        mask = jnp.arange(sq)[:, None] >= jnp.arange(sk)[None, :]
        s = jnp.where(mask, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("...qk,...kd->...qd", p.astype(v.dtype), v,
                      preferred_element_type=jnp.float32,
                      precision=prec).astype(v.dtype)


# Largest block the Pallas kernels tile queries and keys by.  Measured on a
# v5e at [32 heads, S 4096, 192-wide keys, 128-wide values], bf16, causal
# (PERF.md, PR 28): forward / backward 7.42 / 21.44 ms at 128 x 128, 3.27 /
# 7.56 at 256 x 256, 2.34 / 6.56 at 512 x 512, 2.57 / 6.59 at 1024 x 512;
# 1024-wide key blocks do not fit the kernels' VMEM.
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


# Below this sequence length the XLA attention (batched matmuls + fused
# softmax over a small S×S) beats the Pallas kernel: at S=128 the grid
# degenerates to one K block per cell and Mosaic per-cell overhead
# dominates (profiled on v5e @ BERT-base: 3.9 ms pallas vs ~1 ms XLA fwd).
# The kernel's job is long context, where S×S cannot exist in HBM.
_PALLAS_FWD_MIN_SEQ = int(os.environ.get("MXNET_TPU_FLASH_FWD_MIN_SEQ", "1024"))


def _should_use_pallas(q, k, seq_axis=2):
    """One predicate for the primal AND the VJP forward — custom_vjp needs
    both to pick the same kernel path or eval/train numerics diverge.
    ``seq_axis`` lets bshd-layout callers gate without materializing a
    transpose.  Returns (use, interpret, blocks)."""
    sq, sk = q.shape[seq_axis], k.shape[seq_axis]
    use, interpret = _use_pallas(q)
    if q.dtype == jnp.float16 and not interpret:
        use = False  # Mosaic has no f16; XLA reference path handles it
    if use and not interpret and max(sq, sk) < _PALLAS_FWD_MIN_SEQ:
        use = False
    blocks = _pallas_blocks(sq, sk) if use else None
    return blocks is not None, interpret, blocks


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _flash(q, k, v, causal, scale):
    use, interpret, blocks = _should_use_pallas(q, k)
    if use:
        b, h, s, d = q.shape
        out = _flash_fwd_pallas(
            q.reshape(b * h, s, d), k.reshape(b * h, -1, d),
            v.reshape(b * h, -1, v.shape[-1]),
            causal, scale, interpret, block_q=blocks[0], block_k=blocks[1],
        )
        return out.reshape(b, h, s, v.shape[-1])
    return attention_reference(q, k, v, causal, scale)


# Below this query length the XLA backward (one fused S×S program) beats
# the two-pass blockwise kernel, and above it the blockwise kernel wins on
# both time and (crucially) memory — the XLA path's S×S residuals grow
# quadratically.  Measured on v5e (bf16, causal, D=64): S=128 BERT step
# 809 vs 913 samples/s (XLA wins), S=2048 14.9 vs 11.6 ms, S=4096 16.6 vs
# 14.9 ms, S=8192 25.9 vs 31.1 ms (blockwise wins).
_PALLAS_BWD_MIN_SEQ = int(os.environ.get("MXNET_TPU_FLASH_BWD_MIN_SEQ", "8192"))
# ... and whatever the length, above this many bytes of float32 scores
# ([B, H, Sq, Sk]) the XLA backward's S×S temporaries (scores, probabilities
# and their gradients, several copies) no longer fit beside a model: 32 heads
# at S 4096 are 2 GiB a copy.  The blockwise backward keeps memory linear.
_PALLAS_BWD_MIN_SCORE_BYTES = 1 << 30


def _flash_fwd(q, k, v, causal, scale):
    """VJP forward: on the Pallas path, also save (o, lse) so the backward
    can run blockwise without ever materializing S×S."""
    use, interpret, blocks = _should_use_pallas(q, k)
    if use:
        b, h, s, d = q.shape
        sk, d_v = k.shape[2], v.shape[-1]
        with_lse = (max(s, sk) >= _PALLAS_BWD_MIN_SEQ
                    or 4 * b * h * s * sk >= _PALLAS_BWD_MIN_SCORE_BYTES)
        res = _flash_fwd_pallas(
            q.reshape(b * h, s, d), k.reshape(b * h, sk, d),
            v.reshape(b * h, sk, d_v),
            causal, scale, interpret, block_q=blocks[0], block_k=blocks[1],
            with_lse=with_lse)
        if with_lse:
            out, lse = res
            out = out.reshape(b, h, s, d_v)
            return out, (q, k, v, out, lse, interpret)
        return res.reshape(b, h, s, d_v), (q, k, v, None, None, False)
    out = attention_reference(q, k, v, causal, scale)
    return out, (q, k, v, None, None, False)


def _flash_bwd(causal, scale, res, do):
    q, k, v, o, lse, interpret = res
    if lse is not None:
        b, h, s, d = q.shape
        sk, d_v = k.shape[2], v.shape[-1]
        blocks = _pallas_blocks(s, sk)
        dq, dk, dv = _flash_bwd_pallas(
            q.reshape(b * h, s, d), k.reshape(b * h, sk, d),
            v.reshape(b * h, sk, d_v), do.reshape(b * h, s, d_v),
            o.reshape(b * h, s, d_v), lse, causal, scale, interpret,
            block_q=blocks[0], block_k=blocks[1])
        return (dq.reshape(q.shape), dk.reshape(k.shape), dv.reshape(v.shape))
    return _flash_bwd_xla(causal, scale, (q, k, v), do)


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


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(q, k, v, causal=False, scale=None):
    """Fused attention on [B, H, S, D] arrays; differentiable; bf16-safe."""
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    return _flash(q, k, v, causal, float(scale))


# ---------------------------------------------------------------------------
# [B, S, H, Dh] layout path — no materialized head transposes (short-seq XLA
# tier; the layout shuffles live inside the dot_generals where the MXU's
# layout assignment absorbs them)
# ---------------------------------------------------------------------------


def _causal_mask(s):
    sq, sk = s.shape[-2], s.shape[-1]
    mask = jnp.arange(sq)[:, None] >= jnp.arange(sk)[None, :]
    return jnp.where(mask, s, -jnp.inf)


# Score-tensor layout for the bshd XLA path: 'bhqk' (default — heads on
# the major axes) or 'bqhk' (heads inboard; an A/B candidate for the
# profiled head-split relayout copies on TPU — numerically identical,
# pinned by test).  Fixed at import; ONE code path parameterized by the
# einsum subscript so the math cannot diverge between layouts.
_SL = ("bqhk" if os.environ.get("MXNET_TPU_ATTN_SCORE_LAYOUT", "bhqk")
       == "bqhk" else "bhqk")


def _causal_mask_bqhk(s):
    sq, sk = s.shape[1], s.shape[-1]
    mask = (jnp.arange(sq)[:, None, None] >= jnp.arange(sk)[None, None, :])
    return jnp.where(mask, s, -jnp.inf)


_SCORE_MASK = _causal_mask_bqhk if _SL == "bqhk" else _causal_mask


def attention_reference_bshd(q, k, v, causal=False, scale=None):
    """Plain jnp attention over [B, S, H, Dh] operands (head axis stays in
    place; same fp32-accumulate / fp32-softmax policy as
    :func:`attention_reference`)."""
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    prec = (jax.lax.Precision.HIGHEST if q.dtype == jnp.float32
            else jax.lax.Precision.DEFAULT)
    s = jnp.einsum(f"bqhd,bkhd->{_SL}", q, k,
                   preferred_element_type=jnp.float32, precision=prec) * scale
    if causal:
        s = _SCORE_MASK(s)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum(f"{_SL},bkhd->bqhd", p.astype(v.dtype), v,
                      preferred_element_type=jnp.float32,
                      precision=prec).astype(v.dtype)


# Probs-saving backward: below this many elements in the [B, H, Sq, Sk]
# score tensor, the fwd saves bf16 probabilities and the backward reuses
# them instead of recomputing scores+softmax.  Default 0 = ALWAYS
# rematerialize: measured on-chip (BERT-base B=64 S=128) saving probs
# LOST ~3% end-to-end (1367 vs 1407 samples/s) — the saved tensor's
# write+read broke XLA's fusion of the recompute into the backward
# matmuls, costing more than the recompute it avoided.  The knob stays
# for configs where the trade flips.
_SAVE_PROBS_MAX_ELEMS = int(os.environ.get(
    "MXNET_TPU_ATTN_SAVE_PROBS_MAX_ELEMS", "0"))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _flash_bshd(q, k, v, causal, scale):
    return attention_reference_bshd(q, k, v, causal, scale)


def _save_probs(q, k):
    b, sq, h, _ = q.shape
    return b * h * sq * k.shape[1] <= _SAVE_PROBS_MAX_ELEMS


def _flash_bshd_fwd(q, k, v, causal, scale):
    if not _save_probs(q, k):
        return attention_reference_bshd(q, k, v, causal, scale), (q, k, v, None)
    prec = (jax.lax.Precision.HIGHEST if q.dtype == jnp.float32
            else jax.lax.Precision.DEFAULT)
    mm = functools.partial(jnp.einsum, preferred_element_type=jnp.float32,
                           precision=prec)
    s = mm(f"bqhd,bkhd->{_SL}", q, k) * scale
    if causal:
        s = _SCORE_MASK(s)
    pc = jax.nn.softmax(s, axis=-1).astype(v.dtype)  # bf16 probs, saved
    o = mm(f"{_SL},bkhd->bqhd", pc, v).astype(v.dtype)
    return o, (q, k, v, pc)


def _flash_bshd_bwd(causal, scale, res, do):
    """bshd attention backward.  With saved probs (short seq): classic
    gradient algebra, delta via the flash identity rowsum(dp∘p) — no
    recompute, no fp32 S×S round-trips, and ``o`` need not be saved.
    Without (long seq): rematerialize, the bshd twin of
    :func:`_flash_bwd_xla`."""
    q, k, v, pc = res
    prec = (jax.lax.Precision.HIGHEST if q.dtype == jnp.float32
            else jax.lax.Precision.DEFAULT)
    mm = functools.partial(jnp.einsum, preferred_element_type=jnp.float32,
                           precision=prec)
    if pc is None:
        s = mm(f"bqhd,bkhd->{_SL}", q, k) * scale
        if causal:
            s = _SCORE_MASK(s)
        p = jax.nn.softmax(s, axis=-1)               # fp32, _SL layout
        pc = p.astype(v.dtype)
    else:
        p = pc
    dv = mm(f"{_SL},bqhd->bkhd", pc, do)
    dp = mm(f"bqhd,bkhd->{_SL}", do, v)
    # delta_q = Σ_k dp∘p  (== Σ_d do∘o, the flash identity — saves o)
    delta = jnp.sum(dp * p, axis=-1, keepdims=True)
    ds = (p * (dp - delta)).astype(q.dtype)
    dq = mm(f"{_SL},bkhd->bqhd", ds, k) * scale
    dk = mm(f"{_SL},bqhd->bkhd", ds, q) * scale
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


_flash_bshd.defvjp(_flash_bshd_fwd, _flash_bshd_bwd)


def _attend_bshd(q, k, v, causal, scale):
    """Dispatch [B, S, H, Dh] attention: bshd XLA path at short sequence
    lengths, transpose + Pallas flash kernel at long ones (where the two
    transposes are noise against O(S²) attention)."""
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    # one shared gate with the bhsd path (seq_axis=1 in this layout) so
    # interpret-mode/f16/threshold behavior cannot drift; transposes only
    # happen on the Pallas branch
    use, _, _ = _should_use_pallas(q, k, seq_axis=1)
    if use:
        t = lambda x: x.transpose(0, 2, 1, 3)
        out = _flash(t(q), t(k), t(v), causal, float(scale))
        return out.transpose(0, 2, 1, 3)
    return _flash_bshd(q, k, v, causal, float(scale))


from .registry import register  # noqa: E402


@register("fused_attention")
def fused_attention(q, k, v, num_heads=1, causal=False, scale=None):
    """[B, S, D] convenience form: split heads → flash attention → merge
    (``v`` [B, S, H·Dv] with its own head size).  Registered so it is reachable as ``nd.fused_attention`` /
    ``nd.contrib.fused_attention`` (the role cuDNN fused MHA plays for the
    reference's GPU builds)."""
    b, s, d = q.shape
    h = num_heads
    if d % h or v.shape[-1] % h or k.shape[-1] != d:
        raise ValueError(f"feature dims {d}/{k.shape[-1]}/{v.shape[-1]} do "
                         f"not split into num_heads {h}")

    def split(x):
        return x.reshape(b, x.shape[1], h, x.shape[-1] // h)

    # v may be narrower or wider a head than q and k (latent attention:
    # 192-wide queries and keys, 128-wide values); the output has v's width
    out = _attend_bshd(split(q), split(k), split(v), causal, scale)
    return out.reshape(b, s, v.shape[-1])


@register("fused_qkv_attention")
def fused_qkv_attention(qkv, num_heads=1, causal=False, scale=None):
    """Self-attention straight from the fused QKV projection output
    [B, S, 3·D]: the q/k/v split AND the head split are one free reshape
    ([B, S, 3, H, Dh] decomposes the projection's output columns exactly),
    and the bshd attention core never materializes a head transpose."""
    b, s, d3 = qkv.shape
    h = num_heads
    d = d3 // 3
    if d % h or d3 % 3:
        raise ValueError(f"qkv dim {d3} not divisible into 3 heads×{h}")
    x = qkv.reshape(b, s, 3, h, d // h)
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


def apply_rotary(x, cos, sin):
    """Rotate the interleaved pairs ``(x[2i], x[2i+1])`` of the last axis of
    ``x`` [B, S, H, dim] by the position's angles; the result is laid out as
    the DeepSeek-V3 modeling file leaves it (first halves, then second
    halves), which a dot product of two rotated vectors does not see."""
    pairs = x.astype(jnp.float32).reshape(x.shape[:-1] + (x.shape[-1] // 2, 2))
    a, b = pairs[..., 0], pairs[..., 1]
    c, s = cos[None, :, None, :], sin[None, :, None, :]
    return jnp.concatenate([a * c - b * s, b * c + a * s], -1).astype(x.dtype)


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
