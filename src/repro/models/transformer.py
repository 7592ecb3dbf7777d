"""Model assembly: stages -> scan-over-layers, init/apply/decode.

Every architecture is a list of stages (repeat, blocks); parameters for
a stage are stacked along the repeat dim and the stage runs as
`jax.lax.scan` (small HLO => tractable 512-way SPMD compiles).  zamba2's
shared blocks' weights (params["shared"], one dict per block) live
outside the scan and are closed over (true weight sharing), as is the
embedding output x0 they read; each use's own weights are stacked in the
stage like any block's.  A shared block's output t is held until the
next mamba2 block of the same stage, whose norm input it joins.

Vocab sizes are padded to a multiple of 256 so embeddings/logits shard
over tp (standard practice; loss is computed over the padded vocab).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..distributed.sharding import (DP, EMBED_D, FSDP, SP, TP, VOCAB,
                                    shard, logical_spec)
from .attention import (attn_block, attn_decode, attn_specs,
                        cache_head_dim, init_attn_block, init_shared_block,
                        init_shared_uses, shared_block, shared_block_decode,
                        shared_block_specs, shared_uses_specs)
from .common import F32, cross_entropy, rms_norm
from .config import ModelConfig
from .mamba2 import (init_mamba2, mamba2_mixer, mamba2_specs, mamba2_step)
from .moe import init_moe, moe_ffn, moe_specs


def padded_vocab(cfg: ModelConfig) -> int:
    return (cfg.vocab + 255) // 256 * 256


# ----------------------------------------------------------------------
# init
# ----------------------------------------------------------------------
def _init_block(key, cfg, block, n_copies):
    if block.kind == "attn":
        return init_attn_block(key, cfg, cfg.d_ff, n_copies)
    if block.kind == "shared_attn":   # the block is in params["shared"]
        return init_shared_uses(key, cfg, n_copies)
    if block.kind == "moe":
        k1, k2 = jax.random.split(key)
        p = init_attn_block(k1, cfg, 1, n_copies)
        for w in ("w_gate", "w_up", "w_down"):
            del p[w]
        p["moe"] = init_moe(k2, cfg, n_copies)
        return p
    if block.kind == "mamba2":
        return init_mamba2(key, cfg, n_copies)
    raise ValueError(block.kind)


def init_params(key, cfg: ModelConfig):
    V = padded_vocab(cfg)
    d = cfg.d_model
    keys = jax.random.split(key, 4 + len(cfg.stages))
    dt = jnp.dtype(cfg.dtype)
    params = {
        "embed": (jax.random.normal(keys[0], (V, d), F32) * d ** -0.5
                  ).astype(dt),
        "final_norm": jnp.zeros(d, dt),
        "stages": [],
        "shared": None,
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = (jax.random.normal(keys[1], (d, V), F32)
                             * d ** -0.5).astype(dt)
    if cfg.n_mem_blocks:
        params["shared"] = [
            init_shared_block(k, cfg)
            for k in jax.random.split(keys[2], cfg.n_mem_blocks)]
    for si, (repeat, blocks) in enumerate(cfg.stages):
        bkeys = jax.random.split(keys[3 + si], len(blocks))
        stage = {f"b{bi}": _init_block(bkeys[bi], cfg, blocks[bi], repeat)
                 for bi in range(len(blocks))}
        params["stages"].append(stage)
    return params


def _block_specs(cfg, block, stacked=True, moe_ff_sharded=False):
    if block.kind == "attn":
        return attn_specs(stacked)
    if block.kind == "shared_attn":
        return shared_uses_specs()
    if block.kind == "moe":
        s = attn_specs(stacked)
        for w in ("w_gate", "w_up", "w_down"):
            del s[w]
        s["moe"] = moe_specs(stacked, ff_sharded=moe_ff_sharded)
        return s
    if block.kind == "mamba2":
        return mamba2_specs(stacked, cfg.ssm_conv_bias)
    raise ValueError(block.kind)


def logical_param_specs(cfg: ModelConfig, moe_ff_sharded: bool = False):
    specs = {
        "embed": (VOCAB, EMBED_D),
        "final_norm": (None,),
        "stages": [],
        "shared": None,
    }
    if not cfg.tie_embeddings:
        specs["lm_head"] = (EMBED_D, VOCAB)
    if cfg.n_mem_blocks:
        specs["shared"] = [shared_block_specs()
                           for _ in range(cfg.n_mem_blocks)]
    for repeat, blocks in cfg.stages:
        specs["stages"].append(
            {f"b{bi}": _block_specs(cfg, blocks[bi],
                                    moe_ff_sharded=moe_ff_sharded)
             for bi in range(len(blocks))})
    return specs


def param_specs(params, cfg: ModelConfig, mesh, dp_axes=("data",),
                tp_axes=("model",), fsdp_axes=("data",),
                vocab_axes=("model",), embed_d_axes=("data",),
                moe_ff_sharded: bool = False):
    """Concrete PartitionSpecs: logical axes apply only where the dim
    divides the bound mesh axes (e.g. gemma3's 8 heads skip a 16-way
    model axis but the FSDP dim still shards)."""
    from jax.sharding import PartitionSpec as P
    binding = {TP: tuple(tp_axes), DP: tuple(dp_axes),
               FSDP: tuple(fsdp_axes), VOCAB: tuple(vocab_axes),
               EMBED_D: tuple(embed_d_axes),
               "tp_fsdp": tuple(tp_axes) + tuple(fsdp_axes),
               "stack": ()}

    def size_of(axes):
        n = 1
        for a in axes:
            n *= mesh.shape[a]
        return n

    logical = logical_param_specs(cfg, moe_ff_sharded=moe_ff_sharded)

    def one(arr, spec):
        if arr is None:
            return None
        out = []
        used: set = set()
        for dim, s in zip(arr.shape, spec):
            axes = binding.get(s, ()) if s is not None else ()
            axes = tuple(a for a in axes if a not in used)
            if axes and dim % size_of(axes) == 0:
                used.update(axes)
                out.append(axes[0] if len(axes) == 1 else axes)
            else:
                out.append(None)
        return P(*out)

    return jax.tree.map(one, params, logical,
                        is_leaf=lambda x: x is None or isinstance(x, tuple))


# ----------------------------------------------------------------------
# forward (train / prefill)
# ----------------------------------------------------------------------
def _apply_block(p, x, cfg, block, collect_cache):
    if block.kind == "attn":
        y, kv = attn_block(p, x, cfg, block.window)
        cache = {"k": kv[0], "v": kv[1]} if collect_cache else None
        return y, cache
    if block.kind == "moe":
        y, kv = attn_block(p, x, cfg, block.window,
                           mlp_fn=lambda h: moe_ffn(p["moe"], h, cfg))
        cache = {"k": kv[0], "v": kv[1]} if collect_cache else None
        return y, cache
    raise ValueError(block.kind)


def forward_hidden(params, cfg: ModelConfig, tokens, *, frontend_emb=None,
                   return_cache: bool = False):
    """tokens: (B, S) int32 -> final normed hidden (B, S, d) [, cache]."""
    x = jnp.take(params["embed"], tokens, axis=0)
    if frontend_emb is not None:   # vision/audio stub: replace a prefix
        P_ = frontend_emb.shape[1]
        x = jnp.concatenate(
            [frontend_emb.astype(x.dtype), x[:, P_:]], axis=1)
    x = shard(x, DP, SP, None)   # SP: residual stream sequence-sharded
    x0 = x                       # what zamba2's shared blocks read
    caches = []
    for (repeat, blocks), stage_p in zip(cfg.stages, params["stages"]):
        def body(xc, lp):
            new_cache = {}
            t = None             # a shared block's output, for the next mamba2
            for bi, block in enumerate(blocks):
                p = lp.get(f"b{bi}")
                if block.kind == "shared_attn":
                    t, kv = shared_block(params["shared"][block.mem], p,
                                         xc, x0, cfg)
                    c = {"k": kv[0], "v": kv[1]} if return_cache else None
                elif block.kind == "mamba2":
                    xc, (ssm, conv) = mamba2_mixer(p, xc, cfg, extra=t)
                    t = None
                    c = {"ssm": ssm, "conv": conv} if return_cache else None
                else:
                    xc, c = _apply_block(p, xc, cfg, block, return_cache)
                if c is not None:
                    new_cache[f"b{bi}"] = c
            return xc, (new_cache or None)
        if cfg.remat == "block":
            body = jax.checkpoint(body)
        x, stage_cache = jax.lax.scan(body, x, stage_p, length=repeat)
        caches.append(stage_cache)
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    return (x, caches) if return_cache else x


def _head(params, cfg):
    return params["embed"].T if cfg.tie_embeddings else params["lm_head"]


def forward(params, cfg: ModelConfig, tokens, *, frontend_emb=None,
            return_cache: bool = False):
    """tokens: (B, S) int32 -> logits (B, S, V) [, cache]."""
    out = forward_hidden(params, cfg, tokens, frontend_emb=frontend_emb,
                         return_cache=return_cache)
    x, caches = out if return_cache else (out, None)
    logits = jnp.einsum("bsd,dv->bsv", x, _head(params, cfg))
    # keep the f32 CE small: S-sharded when SP is bound, else V-sharded
    from ..distributed.sharding import axis_size
    logits = shard(logits, DP, SP, None) if axis_size(SP) > 1 \
        else shard(logits, DP, None, TP)
    if return_cache:
        return logits, caches
    return logits


def loss_fn(params, cfg: ModelConfig, tokens, labels, frontend_emb=None,
            ce_chunk: int = 1024):
    """Chunked CE: (B, S, V) logits are never materialized (262k-vocab
    cells would otherwise dominate peak memory)."""
    from .common import chunked_cross_entropy
    x = forward_hidden(params, cfg, tokens, frontend_emb=frontend_emb)
    return chunked_cross_entropy(x, _head(params, cfg), labels,
                                 chunk=ce_chunk)


# ----------------------------------------------------------------------
# decode
# ----------------------------------------------------------------------
def _cache_len(block, cfg, s_max):
    if block.kind == "mamba2":
        return None
    return min(block.window, s_max) if block.window else s_max


def init_cache(cfg: ModelConfig, batch: int, s_max: int):
    """Zeroed decode cache.  Windowed layers use ring buffers of length
    min(window, s_max); mamba2 blocks carry (ssm, conv) states."""
    dt = jnp.dtype(cfg.dtype)
    caches = []
    for repeat, blocks in cfg.stages:
        stage = {}
        for bi, block in enumerate(blocks):
            if block.kind == "mamba2":
                nh, hp, ns = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state
                stage[f"b{bi}"] = {
                    "ssm": jnp.zeros((repeat, batch, nh, ns, hp), F32),
                    "conv": jnp.zeros((repeat, batch, cfg.ssm_conv - 1,
                                       cfg.ssm_conv_dim), dt)}
            else:
                S = _cache_len(block, cfg, s_max)
                # sequence-major, hd padded (see attn_decode layout note)
                kv = (repeat, S, cfg.n_kv_heads, batch, cache_head_dim(cfg))
                if cfg.kv_quant:
                    # int8 payload + per-(token, head) f32 scales:
                    # halves cache bytes (the decode bandwidth floor)
                    stage[f"b{bi}"] = {
                        "k": jnp.zeros(kv, jnp.int8),
                        "v": jnp.zeros(kv, jnp.int8),
                        "k_scale": jnp.zeros(kv[:-1], F32),
                        "v_scale": jnp.zeros(kv[:-1], F32)}
                else:
                    stage[f"b{bi}"] = {"k": jnp.zeros(kv, dt),
                                       "v": jnp.zeros(kv, dt)}
        caches.append(stage)
    return caches


def cache_specs(cache, mesh, dp_axes=("data",), tp_axes=("model",),
                seq_axes=None):
    """KV caches: batch over dp, sequence over `seq_axes` (default tp —
    SP for inference; long_500k binds seq to ("data","model") for a
    256-way split of the 512k cache).  SSM states: batch over dp, heads
    over tp."""
    from jax.sharding import PartitionSpec as P
    seq_axes = tuple(seq_axes if seq_axes is not None else tp_axes)

    def size_of(axes):
        n = 1
        for a in axes:
            n *= mesh.shape[a]
        return n

    def ax(axes, dim):
        if not axes or dim % size_of(axes) != 0:
            return None
        return axes[0] if len(axes) == 1 else axes

    def one(path, arr):
        name = path[-1].key if hasattr(path[-1], "key") else str(path[-1])
        if name in ("k", "v"):          # (r, S, KV, B, hdc)
            return P(None, ax(seq_axes, arr.shape[1]), None,
                     ax(tuple(dp_axes), arr.shape[3]), None)
        if name in ("k_scale", "v_scale"):   # (r, S, KV, B) int8 scales
            return P(None, ax(seq_axes, arr.shape[1]), None,
                     ax(tuple(dp_axes), arr.shape[3]))
        if name == "ssm":               # (r, B, nh, ns, hp)
            return P(None, ax(tuple(dp_axes), arr.shape[1]),
                     ax(tuple(tp_axes), arr.shape[2]), None, None)
        if name == "conv":              # (r, B, K-1, conv_dim)
            return P(None, ax(tuple(dp_axes), arr.shape[1]), None,
                     ax(tuple(tp_axes), arr.shape[3]))
        return P()

    return jax.tree_util.tree_map_with_path(one, cache)


def decode_step(params, cfg: ModelConfig, cache, tokens, pos):
    """One decode step.  tokens: (B,) int32; pos: scalar int32 (current
    length, i.e. the position being written).  Returns (logits, cache).

    Each stage's stacked cache rides in the layer scan's carry; the scan's
    xs are the stage's parameters and the layer index.  A layer reads its
    slab in place and writes back only what changed: an attention block
    (a shared block's use too) the new token (and its int8 scales) at
    (layer, slot), a mamba2 block its (ssm, conv) state.  Nothing
    cache-sized is sliced out as xs or restacked as ys, so with the
    donated cache the step updates it where it lies.  Each shared-block
    use and each mamba2 layer is a `jax.named_scope` (`shared_block`,
    `mamba2`), which groups its ops in a device profile."""
    x = jnp.take(params["embed"], tokens, axis=0)    # (B, d)
    x = shard(x, DP, None)
    x0 = x                       # what zamba2's shared blocks read
    new_caches = []
    for (repeat, blocks), stage_p, stage_c in zip(
            cfg.stages, params["stages"], cache):
        def body(carry, inp):
            xc, sc = carry
            lp, i = inp
            sc = dict(sc)
            t = None             # a shared block's output, for the next mamba2
            for bi, block in enumerate(blocks):
                key = f"b{bi}"
                p = lp.get(key)
                c = sc[key]
                if block.kind == "shared_attn":
                    with jax.named_scope("shared_block"):
                        t, sc[key] = shared_block_decode(
                            params["shared"][block.mem], p, xc, x0, c, i,
                            pos, cfg)
                elif block.kind == "mamba2":
                    with jax.named_scope("mamba2"):
                        state = [jax.lax.dynamic_index_in_dim(c[n], i, 0,
                                                              False)
                                 for n in ("ssm", "conv")]
                        xc, (ssm, conv) = mamba2_step(p, xc, state, cfg,
                                                      extra=t)
                        t = None
                        sc[key] = {
                            "ssm": jax.lax.dynamic_update_index_in_dim(
                                c["ssm"], ssm, i, 0),
                            "conv": jax.lax.dynamic_update_index_in_dim(
                                c["conv"], conv, i, 0)}
                else:
                    W = c["k"].shape[1]
                    if block.window and W <= block.window:
                        slot = pos % W       # ring buffer
                        eff_window = None    # whole ring is the window
                    else:
                        slot = None
                        eff_window = block.window
                    xc, sc[key] = attn_decode(
                        p, xc, c, i, pos, cfg, eff_window,
                        mlp_fn=(lambda h, p_=p: moe_ffn(
                            p_["moe"], h, cfg, dropless=True))
                        if block.kind == "moe" else None,
                        slot=slot)
            return (xc, sc), None
        (x, new_c), _ = jax.lax.scan(
            body, (x, stage_c), (stage_p, jnp.arange(repeat)),
            length=repeat)
        new_caches.append(new_c)
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = jnp.einsum("bd,dv->bv", x, head)
    return shard(logits, DP, TP), new_caches
