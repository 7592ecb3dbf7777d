"""Pallas TPU decode attention (flash-decode: the serving hot path).

One new token against a long KV cache: grid (B, KVH, n_s) with the
sequence dim innermost-sequential; the per-(batch, kv-head) group of G
query heads rides in VMEM scratch with online-softmax state, so the
cache is streamed HBM->VMEM exactly once per step.  `valid_len` arrives
via scalar prefetch — masked tail tiles are skipped with `pl.when`
(no MXU work for the unwritten cache suffix).

Block shapes: (block_s x D) cache tiles, (G x D) query tile.  For GQA
with G in {4, 8, 16} the (G x block_s) score matmul is sublane-thin but
the streamed cache read is the bottleneck at decode — this kernel is
bandwidth-bound by design (see EXPERIMENTS.md §Roofline decode rows).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

F32 = jnp.float32
NEG_INF = -1e30


def _decode_kernel(vl_ref, q_ref, k_ref, v_ref, o_ref,
                   m_scr, l_scr, acc_scr, *, scale, block_s, n_s):
    si = pl.program_id(2)
    valid_len = vl_ref[0]

    @pl.when(si == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    s_lo = si * block_s

    @pl.when(s_lo < valid_len)
    def _body():
        q = q_ref[0, 0].astype(F32)               # (G, D)
        k = k_ref[0, 0].astype(F32)               # (block_s, D)
        v = v_ref[0, 0].astype(F32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=F32) * scale
        pk = s_lo + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(pk < valid_len, s, NEG_INF)
        m_prev = m_scr[...][:, 0]
        m_new = jnp.maximum(m_prev, s.max(axis=-1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_new = l_scr[...][:, 0] * corr + p.sum(axis=-1)
        pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                 preferred_element_type=F32)
        acc_scr[...] = acc_scr[...] * corr[:, None] + pv
        m_scr[...] = jnp.broadcast_to(m_new[:, None], m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new[:, None], l_scr.shape)

    @pl.when(si == n_s - 1)
    def _finish():
        l = l_scr[...][:, 0]
        o_ref[0, 0] = (acc_scr[...]
                       / jnp.maximum(l, 1e-30)[:, None]).astype(o_ref.dtype)


def decode_attention(q, k_cache, v_cache, valid_len, *,
                     block_s: int = 512, interpret: bool):
    """q: (B, H, D); caches: (B, S, KVH, D); valid_len: scalar int32.
    -> (B, H, D)."""
    B, H, D = q.shape
    _, S, KVH, _ = k_cache.shape
    G = H // KVH
    block_s = min(block_s, S)
    assert S % block_s == 0, (S, block_s)
    n_s = S // block_s

    qg = q.reshape(B, KVH, G, D)
    kh = jnp.swapaxes(k_cache, 1, 2)       # (B, KVH, S, D)
    vh = jnp.swapaxes(v_cache, 1, 2)
    vl = jnp.asarray(valid_len, jnp.int32).reshape(1)

    kernel = functools.partial(_decode_kernel, scale=D ** -0.5,
                               block_s=block_s, n_s=n_s)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, KVH, n_s),
        in_specs=[
            pl.BlockSpec((1, 1, G, D), lambda b, h, si, vl: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, block_s, D),
                         lambda b, h, si, vl: (b, h, si, 0)),
            pl.BlockSpec((1, 1, block_s, D),
                         lambda b, h, si, vl: (b, h, si, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, G, D),
                               lambda b, h, si, vl: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((G, 128), F32),
            pltpu.VMEM((G, 128), F32),
            pltpu.VMEM((G, D), F32),
        ],
    )
    out = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KVH, G, D), q.dtype),
        interpret=interpret,
    )(vl, qg, kh, vh)
    return out.reshape(B, H, D)
