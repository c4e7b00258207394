"""Pipeline parallelism over the 'pp' mesh axis.

The reference has no pipeline parallelism (SURVEY.md §2.3 marks PP as
absent upstream); this is the TPU-native capability the mesh's 'pp' axis
exists for: a GPipe-style microbatch pipeline built from ``shard_map`` +
``lax.ppermute`` over ICI neighbors — stage s computes microbatch m at
tick ``t = s + m``, activations hop one stage per tick, and XLA overlaps
the permute with the next microbatch's compute.

Design notes (TPU-first):
* fixed trip count ``n_micro + P - 1`` and static shapes throughout —
  the bubble is explicit, not dynamic control flow;
* per-stage parameters are a pytree with leading dim P sharded over
  'pp', so each device holds exactly its stage's weights;
* fully differentiable: jax AD reverses the ppermutes, giving the
  backward pipeline for free inside one jitted step.

``pipeline_apply`` composes with the rest of the stack (dp/tp axes can
shard the batch/weights of each stage in the usual way).

This module is the simple FORWARD entry.  Training — microbatched
GPipe/1F1B schedules with an explicitly driven backward, remat options
and bubble accounting — lives in :mod:`parallel.schedule`
(``pipeline_value_and_grad`` / ``SPMDTrainer(stages=...)``); see
docs/pipeline_parallelism.md.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

__all__ = ["pipeline_apply", "stack_stage_params"]


def stack_stage_params(per_stage_params, mesh, axis="pp"):
    """Stack a list of per-stage parameter pytrees along a new leading dim
    and shard that dim over the 'pp' mesh axis.  Returns the stacked
    pytree (each device materializes only its own stage's slice)."""
    stacked = jax.tree_util.tree_map(
        lambda *leaves: jnp.stack(leaves), *per_stage_params)

    def put(leaf):
        spec = P(*((axis,) + (None,) * (leaf.ndim - 1)))
        return jax.device_put(leaf, NamedSharding(mesh, spec))

    return jax.tree_util.tree_map(put, stacked)


def pipeline_apply(stage_fn, stage_params, x, mesh, n_microbatches, axis="pp"):
    """Run ``x`` through P pipeline stages: ``h = stage_fn(params_s, h)``
    for s = 0..P-1, microbatched GPipe-style.

    Parameters
    ----------
    stage_fn : callable(stage_param_slice, h) -> h
        One stage's computation (shapes of h preserved across stages).
    stage_params : pytree
        Leaves with leading dim P, sharded over ``axis`` (see
        :func:`stack_stage_params`).
    x : array [B, ...]
        Batch (replicated over the pp axis; other axes may shard it).
    n_microbatches : int
        Must divide B.
    """
    pp = mesh.shape[axis]
    B = x.shape[0]
    if B % n_microbatches:
        raise ValueError(f"batch {B} not divisible by {n_microbatches} microbatches")
    mb = B // n_microbatches

    in_specs = (
        jax.tree_util.tree_map(
            lambda leaf: P(*((axis,) + (None,) * (leaf.ndim - 1))), stage_params),
        P(),   # x replicated across pp
    )
    out_spec = P()

    def ranked(params, xin):
        s = lax.axis_index(axis)
        # this rank's stage slice (leading dim 1 → squeeze)
        my = jax.tree_util.tree_map(lambda l: l[0], params)
        micro = xin.reshape((n_microbatches, mb) + xin.shape[1:])
        ticks = n_microbatches + pp - 1
        perm = [(i, i + 1) for i in range(pp - 1)]

        def tick(carry, t):
            h_recv, outs = carry
            # stage 0 ingests microbatch t (clamped; masked out later)
            m_idx = jnp.clip(t, 0, n_microbatches - 1)
            feed = lax.dynamic_index_in_dim(micro, m_idx, 0, keepdims=False)
            h_in = jnp.where(s == 0, feed.astype(h_recv.dtype), h_recv)
            h_out = stage_fn(my, h_in)
            # last stage retires microbatch t-(P-1)
            out_idx = jnp.clip(t - (pp - 1), 0, n_microbatches - 1)
            write = jnp.logical_and(s == pp - 1, t >= pp - 1)
            outs = lax.dynamic_update_index_in_dim(
                outs,
                jnp.where(write, h_out,
                          lax.dynamic_index_in_dim(outs, out_idx, 0, False)),
                out_idx, 0)
            h_next = lax.ppermute(h_out, axis, perm)
            return (h_next, outs), None

        h0 = jnp.zeros((mb,) + xin.shape[1:], xin.dtype)
        outs0 = jnp.zeros_like(micro)
        (_, outs), _ = lax.scan(tick, (h0, outs0), jnp.arange(ticks))
        # only the last rank holds real outputs; replicate them to all pp
        # ranks with a masked psum (everyone else contributes zeros)
        outs = lax.psum(jnp.where(s == pp - 1, outs, jnp.zeros_like(outs)), axis)
        return outs.reshape((B,) + xin.shape[1:])

    fn = jax.shard_map(ranked, mesh=mesh, in_specs=in_specs,
                       out_specs=out_spec, check_vma=False)
    return fn(stage_params, x)
