"""Shared model primitives: RMSNorm, RoPE, blocked flash attention
(pure-jnp reference path used for training/prefill lowering — the Pallas
kernels in repro.kernels are drop-in replacements on TPU), decode
attention partials (merged across sequence shards), cross-entropy.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

F32 = jnp.float32


def rms_norm(x, scale, eps: float = 1e-6):
    var = jnp.mean(jnp.square(x.astype(F32)), axis=-1, keepdims=True)
    out = x.astype(F32) * jax.lax.rsqrt(var + eps)
    return (out * (1.0 + scale.astype(F32))).astype(x.dtype)


def rope(x, positions, theta: float):
    """x: (..., S, H, D); positions: (..., S) int32."""
    d = x.shape[-1]
    half = d // 2
    freqs = jnp.exp(-jnp.log(theta) * jnp.arange(0, half, dtype=F32) / half)
    ang = positions[..., :, None].astype(F32) * freqs          # (..., S, half)
    cos = jnp.cos(ang)[..., :, None, :]                        # (..., S, 1, half)
    sin = jnp.sin(ang)[..., :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                          axis=-1)
    return out.astype(x.dtype)


def swiglu(x, w_gate, w_up, w_down):
    g = jnp.einsum("...d,df->...f", x, w_gate)
    u = jnp.einsum("...d,df->...f", x, w_up)
    h = jax.nn.silu(g.astype(F32)).astype(x.dtype) * u
    return jnp.einsum("...f,fd->...d", h, w_down)


# ----------------------------------------------------------------------
# blocked ("flash") attention — pure jnp, O(S) memory via kv-chunk scan
# and a custom VJP that recomputes tiles in the backward pass (without
# it, autodiff saves the stacked (n_kv, B, H, q_chunk, kv_chunk)
# probability tensors: ~2 GiB/layer/device and the dominant HBM term at
# qwen3-235b/train_4k — EXPERIMENTS.md §Perf iteration)
# ----------------------------------------------------------------------
NEG_INF = -1e30


def _tile_mask(pq, pk, causal, window, kv_len):
    mask = (pk < kv_len)[None, :]
    if causal:
        mask = mask & (pk[None, :] <= pq[:, None])
    if window is not None:
        mask = mask & ((pq[:, None] - pk[None, :]) < window)
    return mask


def _pin3(x):
    from ..distributed.sharding import DP, SP, TP, shard
    return shard(x, DP, TP, SP)


def _pin4(x):
    from ..distributed.sharding import DP, SP, TP, shard
    return shard(x, DP, TP, SP, None)


def _flash_fwd_scan(qh, kh, vh, opts):
    """qh/kh/vh: (B, H, S, D) head-major.  Returns (out, lse) in f32."""
    causal, window, q_offset, q_chunk, kv_chunk, kv_len, scale = opts
    B, H, Sq, D = qh.shape
    Skv = kh.shape[2]
    n_q, n_kv = Sq // q_chunk, Skv // kv_chunk

    def q_block(qi, qc):
        pq = q_offset + qi * q_chunk + jnp.arange(q_chunk)

        def kv_step(carry, kj):
            m, l, acc = carry
            kc = jax.lax.dynamic_slice_in_dim(kh, kj * kv_chunk,
                                              kv_chunk, 2)
            vc = jax.lax.dynamic_slice_in_dim(vh, kj * kv_chunk,
                                              kv_chunk, 2)
            pk = kj * kv_chunk + jnp.arange(kv_chunk)
            s = jnp.einsum("bhqd,bhkd->bhqk", qc, kc,
                           preferred_element_type=F32) * scale
            mask = _tile_mask(pq, pk, causal, window, kv_len)
            s = jnp.where(mask[None, None], s, NEG_INF)
            m_new = _pin3(jnp.maximum(m, s.max(axis=-1)))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = _pin3(l * corr + p.sum(axis=-1))
            pv = jnp.einsum("bhqk,bhkd->bhqd", p.astype(vh.dtype), vc,
                            preferred_element_type=F32)
            acc_new = _pin4(acc * corr[..., None] + pv)
            return (m_new, l_new, acc_new), None

        m0 = _pin3(jnp.full((B, H, q_chunk), NEG_INF, F32))
        l0 = _pin3(jnp.zeros((B, H, q_chunk), F32))
        a0 = _pin4(jnp.zeros((B, H, q_chunk, D), F32))
        (m, l, acc), _ = jax.lax.scan(kv_step, (m0, l0, a0),
                                      jnp.arange(n_kv))
        l_safe = jnp.maximum(l, 1e-30)
        return acc / l_safe[..., None], m + jnp.log(l_safe)

    if n_q == 1:
        out, lse = q_block(jnp.int32(0), qh)
    else:
        def scan_q(_, qi):
            qc = jax.lax.dynamic_slice_in_dim(qh, qi * q_chunk,
                                              q_chunk, 2)
            o, s = q_block(qi, qc)
            return None, (_pin4(o), _pin3(s))
        _, (outs, lses) = jax.lax.scan(scan_q, None, jnp.arange(n_q))
        out = jnp.moveaxis(outs, 0, 2).reshape(B, H, Sq, D)
        lse = jnp.moveaxis(lses, 0, 2).reshape(B, H, Sq)
    return out, lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _flash(qh, kh, vh, opts):
    out, _ = _flash_fwd_scan(qh, kh, vh, opts)
    return out


def _flash_fwd(qh, kh, vh, opts):
    out, lse = _flash_fwd_scan(qh, kh, vh, opts)
    return out, (qh, kh, vh, out, lse)


def _flash_bwd(opts, res, dout):
    """Tile-recomputing backward (flash attention backward): O(S)
    residuals, no stacked probability saves."""
    causal, window, q_offset, q_chunk, kv_chunk, kv_len, scale = opts
    qh, kh, vh, out, lse = res
    B, H, Sq, D = qh.shape
    Skv = kh.shape[2]
    n_q, n_kv = Sq // q_chunk, Skv // kv_chunk
    dout = dout.astype(F32)
    Drow = _pin3(jnp.sum(dout * out, axis=-1))          # (B, H, Sq)

    def q_step(carry, qi):
        dk, dv = carry
        qc = jax.lax.dynamic_slice_in_dim(qh, qi * q_chunk, q_chunk, 2)
        doc = jax.lax.dynamic_slice_in_dim(dout, qi * q_chunk,
                                           q_chunk, 2)
        lsec = jax.lax.dynamic_slice_in_dim(lse, qi * q_chunk,
                                            q_chunk, 2)
        Dc = jax.lax.dynamic_slice_in_dim(Drow, qi * q_chunk,
                                          q_chunk, 2)
        pq = q_offset + qi * q_chunk + jnp.arange(q_chunk)

        def kv_step(inner, kj):
            dq_i, dk, dv = inner
            kc = jax.lax.dynamic_slice_in_dim(kh, kj * kv_chunk,
                                              kv_chunk, 2)
            vc = jax.lax.dynamic_slice_in_dim(vh, kj * kv_chunk,
                                              kv_chunk, 2)
            pk = kj * kv_chunk + jnp.arange(kv_chunk)
            s = jnp.einsum("bhqd,bhkd->bhqk", qc, kc,
                           preferred_element_type=F32) * scale
            mask = _tile_mask(pq, pk, causal, window, kv_len)
            p = jnp.where(mask[None, None],
                          jnp.exp(s - lsec[..., None]), 0.0)
            dv_j = jnp.einsum("bhqk,bhqd->bhkd", p, doc,
                              preferred_element_type=F32)
            dp = jnp.einsum("bhqd,bhkd->bhqk", doc, vc.astype(F32),
                            preferred_element_type=F32)
            ds = p * (dp - Dc[..., None]) * scale
            dq_i = _pin4(dq_i + jnp.einsum(
                "bhqk,bhkd->bhqd", ds, kc.astype(F32),
                preferred_element_type=F32))
            dk_j = jnp.einsum("bhqk,bhqd->bhkd", ds, qc.astype(F32),
                              preferred_element_type=F32)
            upd = jax.lax.dynamic_slice_in_dim(dk, kj * kv_chunk,
                                               kv_chunk, 2) + dk_j
            dk = jax.lax.dynamic_update_slice_in_dim(
                dk, upd, kj * kv_chunk, 2)
            upd = jax.lax.dynamic_slice_in_dim(dv, kj * kv_chunk,
                                               kv_chunk, 2) + dv_j
            dv = jax.lax.dynamic_update_slice_in_dim(
                dv, upd, kj * kv_chunk, 2)
            return (dq_i, _pin4(dk), _pin4(dv)), None

        dq0 = _pin4(jnp.zeros((B, H, q_chunk, D), F32))
        (dq_i, dk, dv), _ = jax.lax.scan(kv_step, (dq0, dk, dv),
                                         jnp.arange(n_kv))
        return (dk, dv), dq_i

    dk0 = _pin4(jnp.zeros((B, H, Skv, D), F32))
    dv0 = _pin4(jnp.zeros((B, H, Skv, D), F32))
    if n_q == 1:
        (dk, dv), dq = q_step((dk0, dv0), jnp.int32(0))
    else:
        (dk, dv), dqs = jax.lax.scan(q_step, (dk0, dv0),
                                     jnp.arange(n_q))
        dq = jnp.moveaxis(dqs, 0, 2).reshape(B, H, Sq, D)
    return (dq.astype(qh.dtype), dk.astype(kh.dtype),
            dv.astype(vh.dtype))


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(q, k, v, *, causal: bool = True,
                    window: int | None = None, q_offset: int = 0,
                    q_chunk: int = 1024, kv_chunk: int = 1024,
                    kv_len: int | None = None,
                    scale: float | None = None):
    """q: (B, Sq, H, D); k, v: (B, Skv, KVH, D).  GQA via KV expansion.
    `scale` multiplies the scores (default D ** -0.5).

    Scans kv in chunks with running log-sum-exp so peak memory is
    O(q_chunk * kv_chunk) per head instead of O(Sq * Skv), and a
    custom VJP recomputes tiles in the backward pass.
    `q_offset` is the absolute position of q[0] (prefill continuation).

    Layout note (perf iteration #1, EXPERIMENTS.md §Perf): everything in
    the loop is head-major (B, H, S, D) and *explicitly pinned* to
    head-sharded — GQA via a one-off KVH->H expansion.  The earlier
    (B, KVH, G, S, D) grouped layout made GSPMD flip-flop between
    {KVH,G}-factorized shardings across the scan and fall back to
    "involuntary full rematerialization" (full replication) of the f32
    accumulators: ~100x collective blow-up at llama3-8b/train_4k scale.
    Under context parallelism (SP bound, TP free) the Sq dim is sharded
    and the q-chunk scan is disabled so each device keeps its own
    contiguous S shard.
    """
    from ..distributed.sharding import DP, SP, TP, shard, sp_active
    B, Sq, H, D = q.shape
    _, Skv, KVH, _ = k.shape
    G = H // KVH
    kv_len = Skv if kv_len is None else kv_len
    scale = D ** -0.5 if scale is None else scale
    if G > 1:       # expand KV heads so every loop tensor is H-major
        k = jnp.repeat(k, G, axis=2)
        v = jnp.repeat(v, G, axis=2)
    k = shard(k, DP, None, TP, None)
    v = shard(v, DP, None, TP, None)
    qh = shard(jnp.moveaxis(q, 1, 2), DP, TP, SP, None)
    kh = jnp.moveaxis(k, 1, 2)                              # (B,H,Skv,D)
    vh = jnp.moveaxis(v, 1, 2)

    if sp_active():
        q_chunk = Sq
    q_chunk = min(q_chunk, Sq)
    kv_chunk = min(kv_chunk, Skv)
    assert Sq % q_chunk == 0 and Skv % kv_chunk == 0, (Sq, Skv)
    opts = (causal, window, q_offset, q_chunk, kv_chunk, kv_len, scale)
    out = _flash(qh, kh, vh, opts)
    return jnp.moveaxis(out, 1, 2).astype(q.dtype)      # (B, Sq, H, D)


def decode_attention_partial(q, k_cache, v_cache, valid_len,
                             pos_offset: int = 0, window: int | None = None):
    """One-token attention partials over a (possibly sharded) cache slice.

    q: (B, H, D); caches: (B, S_slice, KVH, D); valid_len: scalar count of
    globally-valid tokens; pos_offset: absolute position of slice[0].
    Returns (o, l, m) — combinable across shards with `merge_partials`.
    """
    B, H, D = q.shape
    _, S, KVH, _ = k_cache.shape
    G = H // KVH
    qg = q.reshape(B, KVH, G, D)
    s = jnp.einsum("bhgd,bkhd->bhgk", qg, k_cache,
                   preferred_element_type=F32) * (D ** -0.5)
    pk = pos_offset + jnp.arange(S)
    mask = pk < valid_len
    if window is not None:
        mask &= pk >= (valid_len - window)
    s = jnp.where(mask[None, None, None], s, NEG_INF)
    m = s.max(axis=-1)                                 # (B, KVH, G)
    p = jnp.exp(s - m[..., None])
    l = p.sum(axis=-1)
    o = jnp.einsum("bhgk,bkhd->bhgd", p.astype(v_cache.dtype), v_cache,
                   preferred_element_type=F32)
    return o, l, m


def merge_partials(parts):
    """Merge [(o, l, m), ...] partial attentions (log-sum-exp algebra)."""
    os, ls, ms = zip(*parts)
    m = jnp.stack(ms).max(axis=0)
    corr = [jnp.exp(mi - m) for mi in ms]
    l = sum(li * ci for li, ci in zip(ls, corr))
    o = sum(oi * ci[..., None] for oi, ci in zip(os, corr))
    return o / jnp.maximum(l, 1e-30)[..., None]


def cross_entropy(logits, labels, z_loss: float = 1e-4):
    """logits: (..., V) in any dtype; labels: (...) int32."""
    logits = logits.astype(F32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    loss = lse - ll
    if z_loss:
        loss = loss + z_loss * jnp.square(lse)
    return loss.mean()


def chunked_cross_entropy(x, head, labels, *, chunk: int = 1024,
                          z_loss: float = 1e-4):
    """CE without materializing (B, S, V) logits (128k–262k vocabs).

    x: (B, S, d) final hidden; head: (d, V); labels: (B, S) int32.
    Scans S in chunks — the per-chunk logits are transient and the
    backward pass recomputes them (sqrt-memory trade identical to
    activation remat).  Returns mean loss over B*S tokens.
    """
    from ..distributed.sharding import DP, VOCAB, shard
    B, S, d = x.shape
    chunk = min(chunk, S)
    if S % chunk:
        chunk = S                      # odd sizes: single chunk
    n = S // chunk
    # chunks replicated along seq (one x all-gather), logits V-sharded
    # over the vocab axis so the f32 softmax is 1/|model| per device
    xc = shard(jnp.moveaxis(x.reshape(B, n, chunk, d), 1, 0),
               None, DP, None, None)
    lc = jnp.moveaxis(labels.reshape(B, n, chunk), 1, 0)

    def step(acc, inp):
        xi, li = inp
        logits = jnp.einsum("bsd,dv->bsv", xi, head).astype(F32)
        logits = shard(logits, DP, None, VOCAB)
        lse = jax.nn.logsumexp(logits, axis=-1)
        ll = jnp.take_along_axis(logits, li[..., None], axis=-1)[..., 0]
        loss = (lse - ll) + (z_loss * jnp.square(lse) if z_loss else 0.0)
        return acc + loss.sum(), None

    total, _ = jax.lax.scan(step, jnp.zeros((), F32), (xc, lc))
    return total / (B * S)
