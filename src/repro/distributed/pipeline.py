"""Microbatch pipeline parallelism over a mesh axis (GPipe-style).

The multi-pod mesh's ``pod`` axis defaults to data parallelism; this
module provides the alternative: treat it as a **stage** axis.  Stages
exchange activations with ``jax.lax.ppermute`` inside ``shard_map`` and
microbatches stream through a scan — the standard collective-permute
pipeline (bubble fraction = (S-1)/(S-1+M) for S stages, M microbatches).

Used by tests and available to the launcher via ``--pipeline``; the
dry-run keeps pod=DP as its default (documented in DESIGN.md §6).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = ["pipeline_apply"]


def pipeline_apply(
    stage_fn,
    stage_params,
    x_microbatches: jax.Array,  # (M, mb, ...) microbatched input
    mesh: Mesh,
    axis: str = "pod",
):
    """Run ``stage_fn(params, x)`` as a pipeline over ``axis``.

    ``stage_params`` must already be sharded so each device along ``axis``
    holds its stage's parameters (leading stage axis).  Returns the final
    stage's outputs for every microbatch, in order.
    """
    n_stages = mesh.shape[axis]
    m = x_microbatches.shape[0]

    def per_stage(params, xs):
        params = jax.tree.map(lambda a: a[0], params)  # drop stage axis
        stage = lax.axis_index(axis)
        n_ticks = m + n_stages - 1
        perm = [(i, i + 1) for i in range(n_stages - 1)]

        def tick(carry, t):
            buf, outs = carry
            # stage 0 feeds a fresh microbatch while available
            mb_idx = jnp.clip(t, 0, m - 1)
            inject = xs[mb_idx]
            x_in = jnp.where(stage == 0, inject, buf)
            y = stage_fn(params, x_in)
            # pass activations to the next stage
            buf_next = lax.ppermute(y, axis, perm)
            # last stage writes its result for microbatch t-(S-1)
            out_idx = jnp.clip(t - (n_stages - 1), 0, m - 1)
            valid = (t >= n_stages - 1) & (stage == n_stages - 1)
            outs = lax.dynamic_update_index_in_dim(
                outs,
                jnp.where(valid, y, outs[out_idx]),
                out_idx,
                0,
            )
            return (buf_next, outs), None

        # mark the carries as device-varying over the stage axis (VMA
        # typing: they become varying after the first ppermute)
        buf0 = lax.pcast(jnp.zeros_like(xs[0]), (axis,), to="varying")
        outs0 = lax.pcast(
            jnp.zeros((m,) + xs.shape[1:], xs.dtype), (axis,), to="varying"
        )
        (_, outs), _ = lax.scan(tick, (buf0, outs0), jnp.arange(n_ticks))
        # broadcast final outputs from the last stage to all stages
        # (masked psum: ppermute needs unique sources)
        outs = lax.psum(
            jnp.where(stage == n_stages - 1, outs, jnp.zeros_like(outs)),
            axis,
        )
        return outs

    fn = jax.shard_map(
        per_stage,
        mesh=mesh,
        in_specs=(P(axis), P()),
        out_specs=P(),
    )
    return fn(stage_params, x_microbatches)
