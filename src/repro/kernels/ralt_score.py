"""Pallas TPU kernel for the RALT exponential-smoothing score update.

The paper's hot path (HotRAP §3.2): every record access updates
(tick, score) with  score' = alpha^(now - tick) * score + hit.  On TPU
the tracker is a dense score table (DESIGN.md #3) updated once per
serving step for every tracked unit (KV pages / experts / vocab rows) —
a bandwidth-bound elementwise sweep that fuses the decay, the hit
accumulation and the hot-set threshold compare into one pass so the
table is read/written exactly once.

Grid: 1-D over row tiles of the (padded) table; blocks of
(block_n // 128, 128).  Outputs: new ticks, new scores, and the is-hot
bitmap (score >= threshold) used by the promotion pathways.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

F32 = jnp.float32


def _ralt_kernel(ticks_ref, scores_ref, hits_ref, now_ref, thresh_ref,
                 new_ticks_ref, new_scores_ref, hot_ref, *, log_alpha):
    now = now_ref[0, 0]
    thresh = thresh_ref[0, 0]
    ticks = ticks_ref[...]
    scores = scores_ref[...].astype(F32)
    hits = hits_ref[...].astype(F32)
    dt = (now - ticks).astype(F32)
    decay = jnp.exp(log_alpha * dt)          # alpha^(now - tick)
    new_scores = scores * decay + hits
    new_ticks_ref[...] = jnp.full_like(ticks, now)
    new_scores_ref[...] = new_scores
    hot_ref[...] = (new_scores >= thresh).astype(jnp.int8)


def ralt_update(ticks, scores, hits, now, threshold, alpha=0.999, *,
                interpret: bool, block_n: int = 1024):
    """ticks: (N,) int32; scores: (N,) f32; hits: (N,) bool/int;
    now/threshold: scalars.  Returns (new_ticks, new_scores, hot_i8).

    The table is padded to a whole number of fixed (rows, 128) blocks,
    rows a multiple of 8, so every block keeps the TPU's (8, 128) tiling
    whatever N is."""
    (N,) = ticks.shape
    lanes = 128
    block_rows = max(block_n // (8 * lanes), 1) * 8
    rows = -(-max(N, 1) // (block_rows * lanes)) * block_rows
    pad = rows * lanes - N

    def to2d(x, fill):
        x = jnp.pad(x, (0, pad), constant_values=fill)
        return x.reshape(rows, lanes)

    t2 = to2d(ticks.astype(jnp.int32), 0)
    s2 = to2d(scores.astype(F32), 0.0)
    h2 = to2d(hits.astype(jnp.int8), 0)
    grid = (rows // block_rows,)
    kernel = functools.partial(_ralt_kernel,
                               log_alpha=math.log(alpha))
    call = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_rows, lanes), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, lanes), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, lanes), lambda i: (i, 0)),
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_rows, lanes), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, lanes), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, lanes), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((rows, lanes), jnp.int32),
            jax.ShapeDtypeStruct((rows, lanes), F32),
            jax.ShapeDtypeStruct((rows, lanes), jnp.int8),
        ],
        interpret=interpret,
    )
    with jax.profiler.TraceAnnotation("ralt_update"):
        nt, ns, hot = call(
            t2, s2, h2,
            jnp.asarray(now, jnp.int32).reshape(1, 1),
            jnp.asarray(threshold, F32).reshape(1, 1))
    return (nt.reshape(-1)[:N], ns.reshape(-1)[:N], hot.reshape(-1)[:N])
