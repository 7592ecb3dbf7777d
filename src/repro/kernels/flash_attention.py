"""Pallas TPU flash attention (train/prefill hot path).

Tiling: grid (B, H, n_q, n_kv) with the kv dim innermost (sequential on
TPU); the online-softmax state (m, l, acc) lives in VMEM scratch and
survives across kv steps.  GQA is native: the k/v BlockSpec index maps
divide the head index by the group size, so KV is never expanded in
HBM.  Causal/windowed blocks that are fully masked are skipped via
`pl.when` (predication — no MXU work issued).

Block shapes: (block_q x D) and (block_k x D) tiles — D (head_dim) is
the lane dim and block_* are multiples of 8 (sublane), so MXU matmuls
are (block_q x D) @ (D x block_k): hardware-aligned for D in
{64, 128, 256}.  VMEM footprint per program:
  q + k + v + acc + p  ~  block_q*D*4 + 2*block_k*D*4 + block_q*D*4
  + block_q*block_k*4  ~  1.3 MiB at (512, 512, D=128) -- well under
the ~16 MiB/core budget, leaving room for double buffering.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

F32 = jnp.float32
NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  scale, causal, window, block_q, block_k, n_kv, kv_len):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_lo = qi * block_q
    k_lo = ki * block_k
    # visibility: skip fully-masked tiles (predication on TPU)
    visible = True
    if causal:
        visible = k_lo <= q_lo + block_q - 1
    if window is not None:
        visible = jnp.logical_and(
            visible, (q_lo - (k_lo + block_k - 1)) < window)

    @pl.when(visible)
    def _body():
        q = q_ref[0, 0].astype(F32)              # (block_q, D)
        k = k_ref[0, 0].astype(F32)              # (block_k, D)
        v = v_ref[0, 0].astype(F32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=F32) * scale
        pq = q_lo + jax.lax.broadcasted_iota(jnp.int32,
                                             (block_q, block_k), 0)
        pk = k_lo + jax.lax.broadcasted_iota(jnp.int32,
                                             (block_q, block_k), 1)
        mask = pk < kv_len
        if causal:
            mask &= pk <= pq
        if window is not None:
            mask &= (pq - pk) < window
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_scr[...][:, 0]                                # (bq,)
        m_new = jnp.maximum(m_prev, s.max(axis=-1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_new = l_scr[...][:, 0] * corr + p.sum(axis=-1)
        pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                 preferred_element_type=F32)
        acc_scr[...] = acc_scr[...] * corr[:, None] + pv
        m_scr[...] = jnp.broadcast_to(m_new[:, None], m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new[:, None], l_scr.shape)

    @pl.when(ki == n_kv - 1)
    def _finish():
        l = l_scr[...][:, 0]
        o_ref[0, 0] = (acc_scr[...]
                       / jnp.maximum(l, 1e-30)[:, None]).astype(o_ref.dtype)


def flash_attention(q, k, v, *, causal: bool = True,
                    window: int | None = None, block_q: int = 512,
                    block_k: int = 512, kv_len: int | None = None,
                    interpret: bool):
    """q: (B, Sq, H, D); k/v: (B, Skv, KVH, D) -> (B, Sq, H, D)."""
    B, Sq, H, D = q.shape
    _, Skv, KVH, _ = k.shape
    G = H // KVH
    kv_len = Skv if kv_len is None else kv_len
    block_q = min(block_q, Sq)
    block_k = min(block_k, Skv)
    assert Sq % block_q == 0 and Skv % block_k == 0, (Sq, Skv)
    n_q, n_kv = Sq // block_q, Skv // block_k

    # head-major for tiling
    qh = jnp.swapaxes(q, 1, 2)       # (B, H, Sq, D)
    kh = jnp.swapaxes(k, 1, 2)       # (B, KVH, Skv, D)
    vh = jnp.swapaxes(v, 1, 2)

    kernel = functools.partial(
        _flash_kernel, scale=D ** -0.5, causal=causal, window=window,
        block_q=block_q, block_k=block_k, n_kv=n_kv, kv_len=kv_len)

    out = pl.pallas_call(
        kernel,
        grid=(B, H, n_q, n_kv),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D),
                         lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, qi, ki: (b, h // G, ki, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, qi, ki: (b, h // G, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, D),
                               lambda b, h, qi, ki: (b, h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Sq, D), q.dtype),
        scratch_shapes=[
            _vmem((block_q, 128), F32),     # m (lane-padded)
            _vmem((block_q, 128), F32),     # l
            _vmem((block_q, D), F32),       # acc
        ],
        interpret=interpret,
    )(qh, kh, vh)
    return jnp.swapaxes(out, 1, 2)


def _vmem(shape, dtype):
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.VMEM(shape, dtype)
