"""GPipe-style pipeline parallelism over a mesh axis.

`gpipe_apply` runs a stack of identical stages (params stacked on the
leading dim, sharded over the pipeline axis) over M microbatches with
the classic (M + S - 1)-tick schedule: activations flow stage->stage
via `collective_permute`, so only adjacent-stage links carry traffic —
the pattern that makes PP the inter-pod parallelism of choice on slow
DCN links (bubble fraction = (S-1)/(M+S-1)).

This is a library feature + correctness artifact (tests run it on a
1-stage degenerate mesh in-process and on a 4-stage mesh in a
subprocess); the production recipes in launch/mesh.py use DP/TP/EP/SP,
with PP available for >2-pod scale-out (DESIGN.md #8).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def gpipe_apply(stage_fn, stage_params, x_micro, *, mesh,
                axis: str = "stage"):
    """stage_fn(params, x) -> y with x/y of identical shape.

    stage_params: pytree with leading dim S (= mesh.shape[axis]),
    sharded over `axis`.  x_micro: (M, ...) microbatches (replicated
    over `axis`).  Returns (M, ...) outputs after all S stages.
    """
    S = mesh.shape[axis]
    M = x_micro.shape[0]
    n_ticks = M + S - 1
    perm = [(i, (i + 1) % S) for i in range(S)]

    pspecs = jax.tree.map(lambda _: P(axis), stage_params)

    @functools.partial(
        jax.shard_map, mesh=mesh,
        in_specs=(pspecs, P()), out_specs=P(),
        check_vma=False)
    def run(params_local, xs):
        sid = jax.lax.axis_index(axis)
        local = jax.tree.map(lambda p: p[0], params_local)

        def tick(carry, t):
            buf, outs = carry
            inject = xs[jnp.clip(t, 0, M - 1)]
            x_in = jnp.where(sid == 0, inject, buf)
            y = stage_fn(local, x_in)
            buf_next = jax.lax.ppermute(y, axis, perm)
            idx = t - (S - 1)
            take = (sid == S - 1) & (idx >= 0)
            outs = jax.lax.dynamic_update_slice_in_dim(
                outs,
                jnp.where(take, y, jax.lax.dynamic_slice_in_dim(
                    outs, jnp.clip(idx, 0, M - 1), 1, 0)[0])[None],
                jnp.clip(idx, 0, M - 1), 0)
            return (buf_next, outs), None

        buf0 = jnp.zeros_like(xs[0])
        outs0 = jnp.zeros_like(xs)
        (_, outs), _ = jax.lax.scan(tick, (buf0, outs0),
                                    jnp.arange(n_ticks))
        # results live on the last stage: share them across the axis
        return jax.lax.psum(
            jnp.where(sid == S - 1, outs, jnp.zeros_like(outs)), axis)

    return run(stage_params, x_micro)


def bubble_fraction(n_stages: int, n_micro: int) -> float:
    return (n_stages - 1) / (n_micro + n_stages - 1)
