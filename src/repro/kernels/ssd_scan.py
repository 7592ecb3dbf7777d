"""Pallas TPU kernel for the Mamba2 SSD chunked scan (train/prefill).

Grid (B, nh, nC) with the chunk dim innermost-sequential; the SSM state
(ns x hp) rides in VMEM scratch across chunks.  Per chunk, one program
computes the within-chunk quadratic term (two (Q x ns)@(ns x Q)-shaped
MXU matmuls + a (Q x Q)@(Q x hp) apply), the inter-chunk contribution
of the carried state, and the state update — the x/B/C/dt chunk tiles
are read from HBM exactly once.

Block shapes: Q (ssm_chunk, default 256) x {hp, ns} tiles; hp=64/ns=128
put the lane dim at 64–128 — hardware-aligned.  VMEM per program:
x(Q,hp) + B/C(Q,ns) + masks (Q,Q) f32 ~ 0.6 MiB at Q=256.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

F32 = jnp.float32


def _ssd_kernel(x_ref, b_ref, c_ref, dt_ref, a_ref, y_ref, hfin_ref,
                h_scr, *, n_chunks, Q):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr)

    xq = x_ref[0, 0].astype(F32)          # (Q, hp)
    Bq = b_ref[0, 0].astype(F32)          # (Q, ns)
    Cq = c_ref[0, 0].astype(F32)          # (Q, ns)
    dtq = dt_ref[0, 0].astype(F32)        # (Q, 128) lane-padded, col 0
    dt_col = dtq[:, 0]                    # (Q,)
    A = a_ref[0, 0]                       # scalar decay rate (negative)

    dA = dt_col * A                       # (Q,)
    La = jnp.cumsum(dA)                   # (Q,)
    # intra-chunk quadratic term
    seg = La[:, None] - La[None, :]       # (Q, Q)
    causal = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0) >= \
        jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    M = jnp.where(causal, jnp.exp(seg), 0.0)
    CB = jax.lax.dot_general(Cq, Bq, (((1,), (1,)), ((), ())),
                             preferred_element_type=F32)   # (Q, Q)
    W = CB * M * dt_col[None, :]
    y = jax.lax.dot_general(W, xq, (((1,), (0,)), ((), ())),
                            preferred_element_type=F32)    # (Q, hp)
    # inter-chunk: contribution of the carried state h (ns, hp)
    h = h_scr[...]
    Ce = Cq * jnp.exp(La)[:, None]                         # (Q, ns)
    y += jax.lax.dot_general(Ce, h, (((1,), (0,)), ((), ())),
                             preferred_element_type=F32)
    y_ref[0, 0] = y.astype(y_ref.dtype)
    # state update
    w = jnp.exp(La[-1] - La) * dt_col                      # (Q,)
    Bw = Bq * w[:, None]                                   # (Q, ns)
    h_new = h * jnp.exp(La[-1]) + jax.lax.dot_general(
        Bw, xq, (((0,), (0,)), ((), ())),
        preferred_element_type=F32)                        # (ns, hp)
    h_scr[...] = h_new

    @pl.when(ci == n_chunks - 1)
    def _finish():
        hfin_ref[0, 0] = h_new


def ssd_scan(x, Bm, Cm, dt, A, *, interpret: bool):
    """x: (B, nC, Q, nh, hp); Bm/Cm: (B, nC, Q, ns); dt: (B, nC, Q, nh);
    A: (nh,) negative decay rates.  h0 = 0.
    Returns (y like x, h_final (B, nh, ns, hp))."""
    Bsz, nC, Q, nh, hp = x.shape
    ns = Bm.shape[-1]

    # head-major layouts for clean tiling
    xh = jnp.transpose(x, (0, 3, 1, 2, 4)).reshape(Bsz, nh, nC * Q, hp)
    dth = jnp.transpose(dt, (0, 3, 1, 2)).reshape(Bsz, nh, nC * Q, 1)
    dth = jnp.broadcast_to(dth, (Bsz, nh, nC * Q, 128))  # lane-pad
    a2 = jnp.broadcast_to(A.astype(F32).reshape(nh, 1, 1),
                          (nh, 1, 1))

    kernel = functools.partial(_ssd_kernel, n_chunks=nC, Q=Q)
    y, hfin = pl.pallas_call(
        kernel,
        grid=(Bsz, nh, nC),
        in_specs=[
            pl.BlockSpec((1, 1, Q, hp), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, Q, ns), lambda b, h, c: (b, c, 0, 0)),
            pl.BlockSpec((1, 1, Q, ns), lambda b, h, c: (b, c, 0, 0)),
            pl.BlockSpec((1, 1, Q, 128), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, 1), lambda b, h, c: (h, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, Q, hp), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, ns, hp), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Bsz, nh, nC * Q, hp), x.dtype),
            jax.ShapeDtypeStruct((Bsz, nh, ns, hp), F32),
        ],
        scratch_shapes=[pltpu.VMEM((ns, hp), F32)],
        interpret=interpret,
    )(xh, Bm, Cm, dth, a2)
    y = y.reshape(Bsz, nh, nC, Q, hp).transpose(0, 2, 3, 1, 4)
    return y.astype(x.dtype), hfin
