"""GQA attention block: train/prefill forward + single-token decode.

Decode keeps the KV cache *sequence-sharded* over the tp axis (SP for
inference): the score/softmax/value contractions over the sharded S dim
lower to partial reductions + small all-reduces instead of gathering
the cache (required to fit 32k x 128 and 500k caches).

zamba2's shared transformer block (`shared_block`, `shared_block_decode`)
reuses the same attention on a wider input.  Use u of block b, with x0
the embedding output (as in transformers' modeling_zamba2.py):

    a = RMSNorm_2d(concat(x, x0))
    o = MHA(a) Wo_b            # q/k/v 2d -> H x hd, rotary over all hd,
                               # softmax scale `attention_scale(cfg)`
    f = RMSNorm_d(o)           # no residual inside the block
    t = (gelu(g) * u) Wdown_b Wlin_u,  [g, u] = f Wgu_b + (f A_u) B_u

and t joins only the next mamba2 block's norm input.  Wo_b .. Wdown_b
are the block's (params["shared"][b]); Wlin_u, A_u, B_u the use's own,
stacked over the stage's repeat.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..distributed.sharding import DP, FSDP, SP, TP, shard
from .common import F32, NEG_INF, flash_attention, rope, swiglu, rms_norm


def init_attn_block(key, cfg, d_ff: int, n_copies: int | None):
    """Params for one attention(+MLP) block; leading dim when stacked."""
    d, H, KV, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 8)
    dt = jnp.dtype(cfg.dtype)

    def mk(k, *shape, fan_in):
        full = shape if n_copies is None else (n_copies, *shape)
        return (jax.random.normal(k, full, F32) * fan_in ** -0.5).astype(dt)

    def zeros(*shape):
        full = shape if n_copies is None else (n_copies, *shape)
        return jnp.zeros(full, dt)

    return {
        "norm1": zeros(d),
        "wq": mk(ks[0], d, H, hd, fan_in=d),
        "wk": mk(ks[1], d, KV, hd, fan_in=d),
        "wv": mk(ks[2], d, KV, hd, fan_in=d),
        "wo": mk(ks[3], H, hd, d, fan_in=H * hd),
        "norm2": zeros(d),
        "w_gate": mk(ks[4], d, d_ff, fan_in=d),
        "w_up": mk(ks[5], d, d_ff, fan_in=d),
        "w_down": mk(ks[6], d_ff, d, fan_in=d_ff),
    }


def _normal(key, shape, fan_in, n_copies, dt):
    full = shape if n_copies is None else (n_copies, *shape)
    return (jax.random.normal(key, full, F32) * fan_in ** -0.5).astype(dt)


def init_shared_block(key, cfg):
    """One shared block: attention on concat(x, x0), 2 d wide, back to d;
    the MLP's gate and up in one matrix."""
    d, H, KV, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    ff = cfg.shared_attn_d_ff
    ks = jax.random.split(key, 6)
    dt = jnp.dtype(cfg.dtype)
    return {
        "norm1": jnp.zeros(2 * d, dt),
        "wq": _normal(ks[0], (2 * d, H, hd), 2 * d, None, dt),
        "wk": _normal(ks[1], (2 * d, KV, hd), 2 * d, None, dt),
        "wv": _normal(ks[2], (2 * d, KV, hd), 2 * d, None, dt),
        "wo": _normal(ks[3], (H, hd, d), H * hd, None, dt),
        "norm2": jnp.zeros(d, dt),
        "w_gu": _normal(ks[4], (d, 2 * ff), d, None, dt),
        "w_down": _normal(ks[5], (ff, d), ff, None, dt),
    }


def init_shared_uses(key, cfg, n_copies: int):
    """The uses' own weights, stacked: the linear layer after the block
    and the rank-r adapter of the MLP's gate/up."""
    d, r, ff = cfg.d_model, cfg.adapter_rank, cfg.shared_attn_d_ff
    ks = jax.random.split(key, 3)
    dt = jnp.dtype(cfg.dtype)
    return {"lin": _normal(ks[0], (d, d), d, n_copies, dt),
            "ad_a": _normal(ks[1], (d, r), d, n_copies, dt),
            "ad_b": _normal(ks[2], (r, 2 * ff), r, n_copies, dt)}


def shared_block_specs():
    return {"norm1": (None,), "wq": (FSDP, TP, None),
            "wk": (FSDP, TP, None), "wv": (FSDP, TP, None),
            "wo": (TP, None, FSDP), "norm2": (None,), "w_gu": (FSDP, TP),
            "w_down": (TP, FSDP)}


def shared_uses_specs():
    return {"lin": ("stack", FSDP, None), "ad_a": ("stack", FSDP, None),
            "ad_b": ("stack", None, TP)}


def attention_scale(cfg) -> float:
    return cfg.head_dim ** -0.5 if cfg.attn_scale is None \
        else cfg.attn_scale


def attn_specs(stacked: bool):
    """PartitionSpec tree (logical dims) matching init_attn_block."""
    r = ("stack",) if stacked else ()
    return {
        "norm1": (*r, None),
        "wq": (*r, FSDP, TP, None),
        "wk": (*r, FSDP, TP, None),      # falls back to None if KV % tp != 0
        "wv": (*r, FSDP, TP, None),
        "wo": (*r, TP, None, FSDP),
        "norm2": (*r, None),
        "w_gate": (*r, FSDP, TP),
        "w_up": (*r, FSDP, TP),
        "w_down": (*r, TP, FSDP),
    }


def _qkv(p, x, positions, cfg):
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    q = rope(q, positions, cfg.rope_theta)
    k = rope(k, positions, cfg.rope_theta)
    return q, k, v


def attn_block(p, x, cfg, window: int | None, positions=None,
               d_ff: int | None = None, mlp_fn=None):
    """Training/prefill forward.  x: (B, S, d).  Returns (y, (k, v))."""
    B, S, _ = x.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(S), (B, S))
    h = rms_norm(x, p["norm1"], cfg.norm_eps)
    o, kv = _attend(p, h, positions, cfg, window)
    x = x + o
    h = rms_norm(x, p["norm2"], cfg.norm_eps)
    if mlp_fn is not None:
        y = mlp_fn(h)
    else:
        y = swiglu(h, p["w_gate"], p["w_up"], p["w_down"])
    x = x + y
    return shard(x, DP, SP, None), kv


def _attend(p, h, positions, cfg, window):
    """Causal attention of the normed input h: (o Wo, (k, v))."""
    S = h.shape[1]
    q, k, v = _qkv(p, h, positions, cfg)
    q = shard(q, DP, None, TP, None)
    k = shard(k, DP, SP, None, None)
    v = shard(v, DP, SP, None, None)
    c = cfg.flash_chunk
    o = flash_attention(q, k, v, causal=True, window=window,
                        q_chunk=min(c, S), kv_chunk=min(c, S),
                        scale=attention_scale(cfg))
    return jnp.einsum("bshk,hkd->bsd", o, p["wo"]), (k, v)


def _shared_mlp(p, use, f):
    """(gelu(g) * u) Wdown Wlin of [g, u] = f Wgu + (f A) B; exact GELU."""
    gu = jnp.einsum("...d,df->...f", f, p["w_gu"]) + jnp.einsum(
        "...r,rf->...f", jnp.einsum("...d,dr->...r", f, use["ad_a"]),
        use["ad_b"])
    ff = p["w_down"].shape[0]
    a = jax.nn.gelu(gu[..., :ff].astype(F32), approximate=False)
    h = a.astype(f.dtype) * gu[..., ff:]
    return jnp.einsum("...d,de->...e",
                      jnp.einsum("...f,fd->...d", h, p["w_down"]),
                      use["lin"])


def shared_block(p, use, x, x0, cfg):
    """Training/prefill forward of one use of a shared block.  x, x0:
    (B, S, d).  Returns (t, (k, v)); no residual is added here."""
    B, S, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(S), (B, S))
    a = rms_norm(jnp.concatenate([x, x0], -1), p["norm1"], cfg.norm_eps)
    o, kv = _attend(p, a, positions, cfg, None)
    f = rms_norm(o, p["norm2"], cfg.norm_eps)
    return shard(_shared_mlp(p, use, f), DP, SP, None), kv


# TPU vector lanes: a minor dimension is laid out in whole multiples of it
LANES = 128


def cache_head_dim(cfg) -> int:
    """The decode cache's head dimension: head_dim padded to whole lanes,
    which the TPU pads to anyway when head_dim is the minor dimension."""
    return -(-cfg.head_dim // LANES) * LANES


def attn_decode(p, x, cache, layer, pos, cfg, window: int | None,
                mlp_fn=None, slot=None):
    """Single-token decode through one layer of a stage's stacked cache.

    x: (B, d).  `cache` holds the stage's caches **sequence-major**, k/v
    (r, S, KV, B, hdc) with hdc = `cache_head_dim` (the pad is zeros),
    sequence-sharded over tp; int8 caches add k_scale/v_scale (r, S, KV,
    B).  `layer` indexes r.  `pos` is the absolute position (RoPE); `slot`
    the cache index to write (ring position for windowed ring buffers,
    defaults to pos).  Returns (y, cache) with the new token written at
    (layer, slot).

    Layout note: the cache is the layer scan's carry and never leaves it.
    The layer's slab is read in place; attention runs over its entries
    other than `slot` (those before `pos`; for a ring, the valid entries
    other than `slot`), with the new token's own score and value joined in
    the same softmax.  The one-token write is then the slab's last use,
    and with S major it is one contiguous block, so XLA updates the
    carried buffer in place.  This is the layout XLA picks for the loop
    when it may choose; padding hd to whole lanes makes it the default
    layout at the jit boundary too (with an unpadded minor hd the TPU's
    default puts S minor, and the loop then converts the whole cache on
    entry and exit).  No (B, S, KV, hd) slab: XLA transposed it per token."""
    h = rms_norm(x, p["norm1"], cfg.norm_eps)
    o, new, sloti = _attend_one(p, h, cache, layer, pos, cfg, window, slot)
    x = x + o
    h = rms_norm(x, p["norm2"], cfg.norm_eps)
    if mlp_fn is not None:
        y = mlp_fn(h)
    else:
        y = swiglu(h, p["w_gate"], p["w_up"], p["w_down"])
    cache = _write_token(cache, new, layer, sloti)
    return shard(x + y, DP, None), cache


def shared_block_decode(p, use, x, x0, cache, layer, pos, cfg):
    """Single-token decode of one use of a shared block through its KV
    cache (laid out as in `attn_decode`).  x, x0: (B, d).  Returns
    (t, cache) with the new token written at (layer, pos)."""
    a = rms_norm(jnp.concatenate([x, x0], -1), p["norm1"], cfg.norm_eps)
    o, new, sloti = _attend_one(p, a, cache, layer, pos, cfg, None, None)
    f = rms_norm(o, p["norm2"], cfg.norm_eps)
    t = _shared_mlp(p, use, f)
    cache = _write_token(cache, new, layer, sloti)
    return shard(t, DP, None), cache


def _attend_one(p, h, cache, layer, pos, cfg, window, slot):
    """One token's attention of the normed input h (B, d_in) over the
    layer's slab and itself: (o Wo, the token's cache entries, the slot
    they go to)."""
    B = h.shape[0]
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    G = H // KV
    S, hdc = cache["k"].shape[1], cache["k"].shape[4]
    scale = attention_scale(cfg)
    positions = jnp.broadcast_to(pos[None], (B, 1)) if pos.ndim == 0 \
        else pos[:, None]
    q = jnp.einsum("bd,dhk->bhk", h, p["wq"])[:, None]
    k = jnp.einsum("bd,dhk->bhk", h, p["wk"])[:, None]
    v = jnp.einsum("bd,dhk->bhk", h, p["wv"])[:, None]
    q = rope(q, positions, cfg.rope_theta)[:, 0]
    k_new = rope(k, positions, cfg.rope_theta)[:, 0]
    v_new = v[:, 0]

    def pad(a):          # head_dim -> the cache's lane-padded head_dim
        return jnp.pad(a, [(0, 0)] * (a.ndim - 1) + [(0, hdc - hd)])

    posi = pos if pos.ndim == 0 else pos[0]
    sloti = posi if slot is None else slot
    quant = cache["k"].dtype == jnp.int8
    if quant:
        # int8 KV: per-(token, head) scales; the cache payload halves
        # (the decode bandwidth floor — §Perf roofline notes)
        ks = jnp.maximum(jnp.abs(k_new).max(-1), 1e-8).astype(F32) / 127
        vs = jnp.maximum(jnp.abs(v_new).max(-1), 1e-8).astype(F32) / 127
        k_w = jnp.clip(jnp.round(k_new.astype(F32) / ks[..., None]),
                       -127, 127).astype(jnp.int8)
        v_w = jnp.clip(jnp.round(v_new.astype(F32) / vs[..., None]),
                       -127, 127).astype(jnp.int8)
    else:
        k_w = k_new.astype(cache["k"].dtype)
        v_w = v_new.astype(cache["v"].dtype)
    k_w, v_w = pad(k_w), pad(v_w)
    slab = {n: shard(jax.lax.dynamic_index_in_dim(c, layer, 0, False),
                     TP, None, DP, *((None,) * (c.ndim - 4)))
            for n, c in cache.items()}
    # scores: the old slab's (partial softmax over the S-sharded cache +
    # all-reduce) and the new token's own
    cdt = F32 if quant else q.dtype
    qg = pad(q.reshape(B, KV, G, hd)).astype(cdt)
    s = jnp.einsum("bhgk,shbk->bhgs", qg, slab["k"].astype(cdt),
                   preferred_element_type=F32) * scale
    s_new = jnp.einsum("bhgk,bhk->bhg", qg, k_w.astype(cdt),
                       preferred_element_type=F32) * scale
    if quant:   # fold the k scales in post-dot (no dequantized cache)
        s = s * jnp.einsum("shb->bhs", slab["k_scale"])[:, :, None]
        s_new = s_new * ks[:, :, None]
    s = shard(s, DP, None, None, TP)
    pk = jnp.arange(S)
    mask = (pk < jnp.minimum(posi + 1, S)) & (pk != sloti)
    if window is not None:
        mask &= pk > (posi - window)
    s = jnp.where(mask[None, None, None], s, NEG_INF)
    m = jnp.maximum(s.max(-1), s_new)          # reductions over sharded S
    e = jnp.exp(s - m[..., None])
    e_new = jnp.exp(s_new - m)
    denom = e.sum(-1) + e_new
    w, w_new = e / denom[..., None], e_new / denom
    if quant:   # fold the v scales into the probabilities
        wv = w * jnp.einsum("shb->bhs", slab["v_scale"])[:, :, None]
        o = jnp.einsum("bhgs,shbk->bhgk", wv, slab["v"].astype(F32),
                       preferred_element_type=F32)
        o = o + (w_new * vs[:, :, None])[..., None] \
            * v_w.astype(F32)[:, :, None]
    else:
        vdt = slab["v"].dtype
        o = jnp.einsum("bhgs,shbk->bhgk", w.astype(vdt), slab["v"],
                       preferred_element_type=F32)
        o = o + w_new.astype(vdt).astype(F32)[..., None] \
            * v_w.astype(F32)[:, :, None]
    o = o[..., :hd].reshape(B, H, hd).astype(h.dtype)
    o = jnp.einsum("bhk,hkd->bd", o, p["wo"])
    new = {"k": k_w, "v": v_w}
    if quant:
        new.update(k_scale=ks, v_scale=vs)
    return o, new, sloti


def _write_token(cache, new, layer, slot):
    """The token write, the slab's last use: in place in the carry."""
    return {n: jax.lax.dynamic_update_slice(
        c, jnp.swapaxes(new[n], 0, 1)[None, None].astype(c.dtype),
        (layer, slot) + (0,) * (c.ndim - 2)) for n, c in cache.items()}
