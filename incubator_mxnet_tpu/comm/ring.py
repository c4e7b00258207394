"""Quantized ring collectives — wire bytes narrow BY CONSTRUCTION.

The PR 14 SPMD exchange (``comm.traced_allreduce``, algo='psum') is a
``quantize → integer psum → dequantize`` sandwich: correct, but the
physical width of the psum is up to XLA — the compiler may widen the
integer reduction and the wire benefit silently evaporates.  EQuARX
(PAPERS.md) builds the quantized allreduce from EXPLICIT per-hop
``ppermute`` steps instead, so what crosses the interconnect at every
hop is the codec's encoded payload (int8 codes + fp32 block scales;
packed int4 nibbles + the uint8/fp32 scale hierarchy) and nothing else —
verifiable from ``cost_analysis`` bytes per hop and the trace's comms
section, whatever XLA decides about the surrounding program.

Three traced primitives (call from a ``shard_map`` body; all return
fp32, accumulate in fp32 ONLY on the local shard):

* :func:`ring_allreduce` — D−1 encoded reduce-scatter hops followed by
  D−1 encoded all-gather hops.  Hop ``t`` of the reduce-scatter
  re-encodes the running partial sum of one chunk and ``ppermute``\\ s it
  to the next device; the all-gather RELAYS each owner's final encoded
  chunk unchanged around the ring, so every device decodes identical
  codes and the result is replicated by construction (the owner also
  applies its own decode — bit-consistency over exactness).  At D=1 the
  ring degenerates to a local encode/decode roundtrip, bit-exact with
  the psum sandwich on one device.
* :func:`ring_reduce_scatter` — the gradient half for fsdp/tp-sharded
  parameter groups: D−1 encoded hops leave device ``i`` holding the
  fully-reduced chunk ``i`` in fp32 (the owned chunk is never encoded
  and never crosses a wire).
* :func:`ring_all_gather` — the parameter half: each device encodes its
  OWN chunk once and the codes relay around the ring (no re-encode, so
  a foreign chunk decodes identically everywhere; the own chunk stays
  exact fp32).

Error feedback: every encode a device performs drops a quantization
error, and each device records each error EXACTLY ONCE (reduce-scatter
hop ``t`` encodes chunk ``(i−t) mod D``; the final broadcast encode
covers the owned chunk — together all D chunk rows).  Summed over
devices the recorded residuals equal the total error the exchange
dropped, so EF-SGD compensation next step is exact in aggregate — the
same contract as the psum form's residual.

Multi-axis: an allreduce over ``("dp", "fsdp")`` runs hierarchically —
ring over the first axis inside each group of the second, then ring
over the second on the (replicated) partial result.  Later-stage
residuals are recorded identically by every member of an already-reduced
group, so they are downweighted by the already-reduced world size to
keep the aggregate-residual invariant.

The replication checker cannot see through ``ppermute`` — wrap bodies
that return ring results replicated with
``jax.shard_map(..., check_vma=False)``.
"""
from __future__ import annotations

from time import perf_counter as _perf

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from .. import profiler as _profiler
from . import compression as _comp

__all__ = [
    "hop_plan", "ring_allreduce", "ring_all_gather", "ring_allreduce_sharded",
    "ring_reduce_scatter", "ring_rs_ag_sharded", "rs_ag_hop_plan",
]


# ---------------------------------------------------------------------------
# per-hop payload codecs (the encoded forms that ride ppermute)
# ---------------------------------------------------------------------------

def _chunk_grain(codec):
    """Chunk-size alignment so hop payloads carry no per-hop padding."""
    return getattr(codec, "block", 1)


def _hop_encode(codec, seg):
    """One chunk -> the tuple of arrays that crosses the wire this hop."""
    if isinstance(codec, _comp.Int8BlockCodec):
        b = _comp._pad_blocks(seg, codec.block)
        s = _comp._block_scales(b, jnp)
        codes = _comp._quantize_codes(
            b, _comp._safe_scales(s, jnp), jnp).astype(jnp.int8)
        return codes, s
    if isinstance(codec, _comp.Int4PackedCodec):
        packed, scodes, tmax, _ = _comp._int4_encode_arrays(
            seg, codec.block, jnp)
        return packed, scodes, tmax
    if isinstance(codec, _comp.Bf16Codec):
        return (seg.astype(jnp.bfloat16),)
    raise TypeError(
        f"ring collectives have no hop payload for {type(codec).__name__}"
        " — teach _hop_encode/_hop_decode its wire form explicitly")


def _hop_decode(codec, payload, n):
    if isinstance(codec, _comp.Int8BlockCodec):
        codes, s = payload
        return _comp._dequantize(
            codes, _comp._safe_scales(s, jnp), n, codec.block, jnp)
    if isinstance(codec, _comp.Int4PackedCodec):
        packed, scodes, tmax = payload
        return _comp._int4_decode_arrays(
            packed, scodes, tmax, n, codec.block, jnp)
    if isinstance(codec, _comp.Bf16Codec):
        return payload[0].astype(jnp.float32)
    raise TypeError(f"no hop decode for {type(codec).__name__}")


def _ppermute(payload, axis_name, perm):
    return tuple(lax.ppermute(p, axis_name, perm) for p in payload)


# ---------------------------------------------------------------------------
# static wire accounting (what the trace/span/benchmark layers report)
# ---------------------------------------------------------------------------

def _ring_chunk(codec, n, world):
    grain = _chunk_grain(codec)
    return -(-n // (world * grain)) * grain


def hop_plan(codec, n, world):
    """Per-hop wire accounting for one D-device ring ALLREDUCE of an
    n-element bucket: ``(hops, bytes_per_hop)`` as sent by EACH device —
    D−1 reduce-scatter hops + D−1 all-gather relays, every one the
    encoded form of one chunk.  ``world <= 1``: nothing crosses a wire.
    """
    if world <= 1:
        return 0, 0
    chunk = _ring_chunk(codec, n, world)
    return 2 * (world - 1), int(codec.wire_nbytes(chunk))


def hop_plan_axes(codec, n, sizes):
    """Aggregate hop accounting for a hierarchical multi-axis ring
    allreduce (one sequential stage per axis, each over the full
    n-element bucket): ``(total_hops, mean_bytes_per_hop)``."""
    hops = wire = 0
    for d in sizes:
        h, b = hop_plan(codec, n, d)
        hops += h
        wire += h * b
    return hops, (wire // hops if hops else 0)


def rs_ag_hop_plan(codec, n, world):
    """Per-hop accounting for the sharded-parameter exchange: a D-device
    quantized reduce-scatter of an n-element gradient bucket plus the
    quantized all-gather of the n-element updated-parameter bucket —
    2(D−1) hops total, each one encoded chunk of n/D elements."""
    if world <= 1:
        return 0, 0
    return 2 * (world - 1), int(codec.wire_nbytes(-(-n // world)))


# ---------------------------------------------------------------------------
# traced primitives (shard_map bodies)
# ---------------------------------------------------------------------------

def _local_roundtrip(codec, comp):
    """The D=1 degenerate form: quantize/dequantize locally — bit-exact
    with the psum sandwich on one device (same grid helpers)."""
    n = comp.shape[0]
    pay = _hop_encode(codec, comp)
    dec = _hop_decode(codec, pay, n)
    return dec, comp - dec


def _ring_allreduce_one(codec, comp, axis_name):
    """Single-axis quantized ring allreduce of the fp32 vector ``comp``
    (identical length on every device).  Returns ``(reduced, err)`` —
    both full length; ``reduced`` is replicated by construction."""
    D = lax.psum(1, axis_name)
    if D == 1:
        return _local_roundtrip(codec, comp)
    n = comp.shape[0]
    my = lax.axis_index(axis_name)
    chunk = _ring_chunk(codec, n, D)
    pad = D * chunk - n
    padded = comp if pad == 0 else jnp.concatenate(
        [comp, jnp.zeros((pad,), comp.dtype)])
    acc = padded.reshape(D, chunk)
    err = acc * 0.0  # derived from acc: carries its device-varying provenance
    perm = [(j, (j + 1) % D) for j in range(D)]

    def rs_hop(t, carry):
        acc, err = carry
        si = (my - t) % D
        send = lax.dynamic_index_in_dim(acc, si, 0, keepdims=False)
        pay = _hop_encode(codec, send)
        err = lax.dynamic_update_index_in_dim(
            err, send - _hop_decode(codec, pay, chunk), si, 0)
        pay = _ppermute(pay, axis_name, perm)
        ri = (my - t - 1) % D
        cur = lax.dynamic_index_in_dim(acc, ri, 0, keepdims=False)
        acc = lax.dynamic_update_index_in_dim(
            acc, cur + _hop_decode(codec, pay, chunk), ri, 0)
        return acc, err

    acc, err = lax.fori_loop(0, D - 1, rs_hop, (acc, err))
    # device my now owns the fully-reduced chunk (my+1)%D; encode it ONCE
    # — the owner decodes its own codes too, so all D devices materialize
    # the identical dequantized chunk (replicated output by construction)
    own = (my + 1) % D
    own_seg = lax.dynamic_index_in_dim(acc, own, 0, keepdims=False)
    pay = _hop_encode(codec, own_seg)
    own_dec = _hop_decode(codec, pay, chunk)
    err = lax.dynamic_update_index_in_dim(err, own_seg - own_dec, own, 0)
    out = lax.dynamic_update_index_in_dim(acc * 0.0, own_dec, own, 0)

    def ag_hop(t, carry):
        out, pay = carry
        pay = _ppermute(pay, axis_name, perm)
        # after t+1 relays we hold the payload device (my−t−1) encoded,
        # i.e. the chunk it owns: ((my−t−1)+1) mod D
        out = lax.dynamic_update_index_in_dim(
            out, _hop_decode(codec, pay, chunk), (my - t) % D, 0)
        return out, pay

    out, _ = lax.fori_loop(0, D - 1, ag_hop, (out, pay))
    return out.reshape(-1)[:n], err.reshape(-1)[:n]


def ring_allreduce(codec, flat, residual, axis_names):
    """Quantized ring allreduce over one or more mesh axes (hierarchical
    for multiple; see the module docstring).  Same contract as
    ``comm.traced_allreduce``: ``flat`` is this shard's local bucket,
    ``residual`` the EF compensation (or None), returns ``(reduced,
    new_residual)`` with ``reduced`` replicated across the axes."""
    if isinstance(axis_names, str):
        axis_names = (axis_names,)
    comp = flat if residual is None else flat + residual
    active = [ax for ax in axis_names if lax.psum(1, ax) > 1]
    if not active:
        return _local_roundtrip(codec, comp)
    x, resid_total, denom = comp, None, 1
    for ax in active:
        x, r = _ring_allreduce_one(codec, x, ax)
        # stage errors after the first are recorded identically by every
        # member of the already-reduced groups: downweight so the sum of
        # residuals over ALL devices still equals the total dropped error
        r = r if denom == 1 else r / denom
        resid_total = r if resid_total is None else resid_total + r
        denom *= int(lax.psum(1, ax))
    return x, resid_total


def ring_reduce_scatter(codec, flat, residual, axis_name):
    """Quantized ring reduce-scatter for sharded parameter groups:
    ``flat`` (length D*S, laid out in ring-chunk order — chunk ``i`` is
    device ``i``'s shard) is summed across the axis with D−1 encoded
    hops; device ``i`` returns its OWN fully-reduced chunk in fp32 (the
    owned chunk never crosses a wire, so it carries no encode error).
    Returns ``(own_chunk [S], err [D*S])`` — the residual covers the
    D−1 chunks this device encoded."""
    comp = flat if residual is None else flat + residual
    D = lax.psum(1, axis_name)
    if D == 1:
        return comp, comp * 0.0
    n = comp.shape[0]
    if n % D:
        raise ValueError(
            f"ring_reduce_scatter needs a bucket divisible by the axis "
            f"size ({n} % {D} != 0) — pad the ring-chunk layout first")
    S = n // D
    acc = comp.reshape(D, S)
    err = acc * 0.0
    my = lax.axis_index(axis_name)
    perm = [(j, (j + 1) % D) for j in range(D)]

    def hop(t, carry):
        acc, err = carry
        si = (my - 1 - t) % D
        send = lax.dynamic_index_in_dim(acc, si, 0, keepdims=False)
        pay = _hop_encode(codec, send)
        err = lax.dynamic_update_index_in_dim(
            err, send - _hop_decode(codec, pay, S), si, 0)
        pay = _ppermute(pay, axis_name, perm)
        ri = (my - 2 - t) % D
        cur = lax.dynamic_index_in_dim(acc, ri, 0, keepdims=False)
        acc = lax.dynamic_update_index_in_dim(
            acc, cur + _hop_decode(codec, pay, S), ri, 0)
        return acc, err

    acc, err = lax.fori_loop(0, D - 1, hop, (acc, err))
    own = lax.dynamic_index_in_dim(acc, my, 0, keepdims=False)
    return own, err.reshape(-1)


def ring_all_gather(codec, shard, axis_name):
    """Quantized ring all-gather for sharded parameter groups: each
    device encodes its OWN chunk once; the codes relay unchanged around
    the ring (D−1 hops), so a foreign chunk decodes identically on every
    device.  Returns the full (D*S,) vector in ring-chunk order — the
    own chunk exact fp32, foreign chunks dequantized."""
    D = lax.psum(1, axis_name)
    if D == 1:
        return shard
    S = shard.shape[0]
    my = lax.axis_index(axis_name)
    perm = [(j, (j + 1) % D) for j in range(D)]
    pay = _hop_encode(codec, shard)
    out = jnp.zeros((D, S), shard.dtype) + (shard * 0.0)[None, :]
    out = lax.dynamic_update_index_in_dim(out, shard, my, 0)

    def hop(t, carry):
        out, pay = carry
        pay = _ppermute(pay, axis_name, perm)
        out = lax.dynamic_update_index_in_dim(
            out, _hop_decode(codec, pay, S), (my - 1 - t) % D, 0)
        return out, pay

    out, _ = lax.fori_loop(0, D - 1, hop, (out, pay))
    return out.reshape(-1)


# ---------------------------------------------------------------------------
# standalone compiled entries (benchmark / evidence / tests) — these are
# the registered compile sites ``comm.ring_allreduce`` / ``comm.ring_rs_ag``
# (docs/observability.md); the training paths fuse the same primitives
# into their own step programs (``spmd.step``, ``gluon.step_fold``).
# ---------------------------------------------------------------------------

_jit_cache = {}


def _compiled(site, key, sig, build):
    """One persistent jitted program per (site, key), with the repo's
    compile accounting: the first call's wall (which includes the
    compile) reports through record_compile, with the lowered stage
    riding along under MXNET_COMPILE_COST=1 for XLA cost accounting."""
    fn = _jit_cache.get(key)
    if fn is not None:
        return fn
    jfn = build()

    def first_call(*args):
        lowered = None
        if _profiler.compile_cost_enabled():
            try:
                lowered = jfn.lower(*args)
            except Exception:
                lowered = None
        t0 = _perf()
        out = jfn(*args)
        _profiler.record_compile(site, sig, (_perf() - t0) * 1e3,
                                 lowered=lowered)
        _jit_cache[key] = jfn
        return out

    return first_call


def ring_allreduce_sharded(codec, flat, mesh, axis_names=("dp",),
                           algo="ring"):
    """Global-array allreduce A/B entry: ``flat`` replicated fp32,
    returns ``(reduced, residual)`` global arrays.  ``algo='ring'`` runs
    the explicit hop exchange (compile site ``comm.ring_allreduce``);
    ``algo='psum'`` the PR 14 sandwich — same codec grid at both ends, so
    the two decode bit-identically at world size 1."""
    axis_names = (axis_names,) if isinstance(axis_names, str) \
        else tuple(axis_names)
    site = "comm.ring_allreduce" if algo == "ring" else "comm.psum_allreduce"
    key = (site, codec.id, axis_names, tuple(flat.shape), str(flat.dtype))
    sig = {"codec": codec.id, "axes": "x".join(axis_names),
           "shape": str(tuple(flat.shape)), "algo": algo}

    def build():
        def body(x):
            return _comp.traced_allreduce(codec, x, None, axis_names,
                                          algo=algo)

        return jax.jit(jax.shard_map(
            body, mesh=mesh, in_specs=(P(),),
            out_specs=(P(), P(axis_names)), check_vma=False))

    fn = _compiled(site, key, sig, build)
    return fn(flat)


def ring_rs_ag_sharded(codec, flat, mesh, axis_name="fsdp"):
    """Global-array sharded-group exchange (compile site
    ``comm.ring_rs_ag``): quantized reduce-scatter of the (replicated
    per-device) gradient bucket followed by the quantized all-gather of
    the reduced shards — the standalone twin of the fsdp step's comm
    structure.  ``flat`` length must divide by the axis size; returns
    ``(gathered, residual)`` global arrays."""
    key = ("comm.ring_rs_ag", codec.id, axis_name, tuple(flat.shape),
           str(flat.dtype))
    sig = {"codec": codec.id, "axes": axis_name,
           "shape": str(tuple(flat.shape))}

    def build():
        def body(x):
            shard, err = ring_reduce_scatter(codec, x, None, axis_name)
            return ring_all_gather(codec, shard, axis_name), err

        return jax.jit(jax.shard_map(
            body, mesh=mesh, in_specs=(P(),),
            out_specs=(P(), P(axis_name)), check_vma=False))

    fn = _compiled("comm.ring_rs_ag", key, sig, build)
    return fn(flat)
