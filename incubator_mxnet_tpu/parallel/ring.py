"""Ring attention — sequence/context parallelism over the 'sp' mesh axis.

Capability the reference does NOT have (SURVEY.md §2.3: its only
sequence-length machinery is BucketingModule padding).  Design follows the
blockwise/ring formulation: each device holds a sequence chunk of Q, K, V;
K/V chunks rotate around the ICI ring via ``lax.ppermute`` while each
device accumulates its queries' attention with an online (flash-style)
softmax, so the full sequence is never materialized on one chip and
communication overlaps compute around the ring.

Two entry points:
* :func:`ring_attention` — per-device body, for use inside ``shard_map``.
* :func:`ring_attention_sharded` — wraps q/k/v global arrays in a
  ``shard_map`` over the mesh ('sp' on the sequence axis).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

__all__ = ["ring_attention", "ring_attention_sharded"]


def _online_update(o, m, l, s, v):
    """Blockwise online-softmax step — shares the masked-row algebra with
    the Pallas flash kernel (ops/attention.py: online_softmax_update);
    ``m``/``l`` carry a trailing keepdim."""
    from ..ops.attention import online_softmax_update

    return online_softmax_update(
        o, m, l, s, v,
        lambda p, v: jnp.einsum("bhqk,bhkd->bhqd", p.astype(v.dtype), v).astype(jnp.float32),
    )


def ring_attention(q, k, v, axis_name="sp", causal=False, scale=None):
    """Attention over a ring of sequence chunks.  Call inside ``shard_map``.

    Shapes (per device): q [B, H, Sq, D], k/v [B, H, Sk, D] where Sq/Sk are
    the LOCAL chunk lengths; global sequence = chunk × ring size, laid out
    in ring order (device i holds positions [i*Sk, (i+1)*Sk)).
    """
    n = lax.psum(1, axis_name)
    my_idx = lax.axis_index(axis_name)
    B, H, Sq, D = q.shape
    Sk = k.shape[2]
    if scale is None:
        scale = 1.0 / math.sqrt(D)
    qf = q.astype(jnp.float32) * scale

    # Derive accumulators from q so they carry its device-varying provenance
    # (jax's shard_map vma check requires loop carries to match).
    o = qf * 0.0
    m = qf[..., :1] * 0.0 - jnp.inf
    l = qf[..., :1] * 0.0
    perm = [(j, (j + 1) % n) for j in range(n)]

    def body(i, carry):
        o, m, l, k_cur, v_cur = carry
        src = (my_idx - i) % n  # whose chunk we currently hold
        s = jnp.einsum("bhqd,bhkd->bhqk", qf, k_cur.astype(jnp.float32))
        if causal:
            q_pos = my_idx * Sq + jnp.arange(Sq)
            k_pos = src * Sk + jnp.arange(Sk)
            mask = q_pos[:, None] >= k_pos[None, :]
            s = jnp.where(mask[None, None], s, -jnp.inf)
        o, m, l = _online_update(o, m, l, s, v_cur)
        # rotate K/V to the next device; on the final iteration the permute
        # restores the original placement (and XLA can elide it).
        k_nxt = lax.ppermute(k_cur, axis_name, perm)
        v_nxt = lax.ppermute(v_cur, axis_name, perm)
        return o, m, l, k_nxt, v_nxt

    o, m, l, _, _ = lax.fori_loop(0, n, body, (o, m, l, k, v)) if n > 1 else body(
        0, (o, m, l, k, v)
    )
    l = jnp.where(l == 0.0, 1.0, l)
    return (o / l).astype(q.dtype)


def ring_attention_sharded(q, k, v, mesh, causal=False, scale=None, batch_axes=("dp", "fsdp")):
    """Global-array entry: q/k/v are [B, H, S, D] jax.Arrays; the sequence
    axis is sharded over 'sp' and batch over ``batch_axes``."""
    spec = P(batch_axes, None, "sp", None)
    fn = functools.partial(ring_attention, causal=causal, scale=scale)
    return jax.shard_map(
        fn,
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
    )(q, k, v)
