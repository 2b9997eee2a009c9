"""Pipeline parallelism via shard_map + collective_permute (GPipe schedule).

The paper's RDU maps pipeline stages spatially on-chip (PCU chains); across
sockets its P2P protocol streams activations between stages fused with
compute (§VII). The TPU analogue: each mesh slice along the 'stage' axis
holds a contiguous block of layers; microbatch activations flow stage→stage
with ``collective_permute`` inside one shard_map — the collective is part of
the same compiled program, so XLA overlaps it with the next microbatch's
compute (the paper's 'collectives fused and pipelined with compute').

Schedule: GPipe-style fill/drain loop, T = M + S - 1 ticks for M microbatches
over S stages. Stage s computes on tick t iff s <= t < s + M.
"""
from __future__ import annotations

import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def pipeline_apply(stage_fn: Callable, stage_params: Any, x: jnp.ndarray,
                   mesh: Mesh, *, axis: str = "stage"):
    """Run ``y = stage_{S-1}(...stage_0(x))`` as a pipeline over mesh axis.

    stage_fn(params_slice, microbatch) -> microbatch (same shape).
    stage_params: pytree with leading dim S (one slice per stage).
    x: (M, ...) microbatches, M >= 1.
    Returns (M, ...) outputs.
    """
    S = mesh.shape[axis]
    M = x.shape[0]
    T = M + S - 1

    pspec_params = jax.tree.map(lambda _: P(axis), stage_params)
    pspec_x = P(axis)  # microbatches land on stage 0; padded layout below

    def body(params, xs):
        # params: (1, ...) this stage's slice; xs: (M_local,...) only stage 0
        # holds real data (we broadcast-pad for shard_map's even-sharding).
        params = jax.tree.map(lambda a: a[0], params)
        idx = jax.lax.axis_index(axis)
        buf = jnp.zeros_like(xs[0])                     # current activation
        outs = jnp.zeros_like(xs)

        def tick(t, carry):
            buf, outs = carry
            # stage 0 ingests microbatch t (if t < M)
            take = jnp.clip(t, 0, M - 1)
            fresh = xs[take]
            buf = jnp.where(idx == 0, jnp.where(t < M, fresh, buf), buf)
            # compute where the stage is active: s <= t < s + M
            active = (idx <= t) & (t < idx + M)
            y = stage_fn(params, buf)
            buf2 = jnp.where(active, y, buf)
            # last stage emits microbatch t - (S-1)
            oidx = jnp.clip(t - (S - 1), 0, M - 1)
            emit = (idx == S - 1) & (t >= S - 1)
            outs = jnp.where(emit, outs.at[oidx].set(buf2), outs)
            # shift: stage s sends to s+1 (ring permute; last->first discarded)
            perm = [(i, (i + 1) % S) for i in range(S)]
            buf3 = jax.lax.ppermute(buf2, axis, perm)
            return buf3, outs

        buf, outs = jax.lax.fori_loop(0, T, tick, (buf, outs))
        # only the last stage holds real outputs; psum replicates them
        return jax.lax.psum(outs, axis)

    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(pspec_params, P(None)),   # x replicated; stage 0 reads it
        out_specs=P(None),
        check_vma=False,
    )
    outs = fn(stage_params, x)
    return outs


def sequential_apply(stage_fn: Callable, stage_params: Any, x: jnp.ndarray):
    """Oracle: apply all stages sequentially to each microbatch."""
    S = jax.tree.leaves(stage_params)[0].shape[0]

    def one(mb):
        h = mb
        for s in range(S):
            ps = jax.tree.map(lambda a: a[s], stage_params)
            h = stage_fn(ps, h)
        return h

    return jax.vmap(one)(x)
