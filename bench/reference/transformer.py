"""Plain reference of the dense decoder the stablelm-3b cells serve.

Per layer, in float32:
    h = rmsnorm(x) * (1 + norm1)
    q, k, v = h Wq, h Wk, h Wv;  rotary on q and k (all head dims,
        halves rotated, frequencies theta^(-i / (hd/2)))
    x = x + softmax(q k^T / sqrt(hd), causal) v Wo
    h = rmsnorm(x) * (1 + norm2)
    x = x + (silu(h Wgate) * (h Wup)) Wdown
then rmsnorm(x) * (1 + final_norm) and the output head.

Departures of the served model from the published stablelm-3b-4e1t, which
this reference shares because it checks the program as configured:
RMSNorm in place of LayerNorm, rotary on every head dimension in place of
a quarter of them (the configuration file's `departures`).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

F32 = jnp.float32
HI = jax.lax.Precision.HIGHEST


def layout(s, vocab_rows: int):
    """Leaf name -> (shape, init) in the serving program's layout.  init
    is ("normal", fan_in) or ("norm",)."""
    d, H, KV, hd, ff, L = (s.d_model, s.n_heads, s.n_kv_heads, s.head_dim,
                           s.d_ff, s.n_layers)
    layer = {
        "norm1": ((L, d), ("norm",)),
        "wq": ((L, d, H, hd), ("normal", d)),
        "wk": ((L, d, KV, hd), ("normal", d)),
        "wv": ((L, d, KV, hd), ("normal", d)),
        "wo": ((L, H, hd, d), ("normal", H * hd)),
        "norm2": ((L, d), ("norm",)),
        "w_gate": ((L, d, ff), ("normal", d)),
        "w_up": ((L, d, ff), ("normal", d)),
        "w_down": ((L, ff, d), ("normal", ff)),
    }
    top = {"embed": ((vocab_rows, d), ("normal", d)),
           "final_norm": ((d,), ("norm",))}
    if not s.tie:
        top["lm_head"] = ((d, vocab_rows), ("normal", d))
    return top, layer


def _leaf(key, shape, init, dtype):
    if init[0] == "norm":       # scales around 0 (the norm multiplies 1 + s)
        return (0.1 * jax.random.normal(key, shape, F32)).astype(dtype)
    return (jax.random.normal(key, shape, F32)
            * init[1] ** -0.5).astype(dtype)


def make_params(s, seed: int, vocab_rows: int):
    """The served weights from the seed, made on the device in one jitted
    call, in the served type."""
    top, layer = layout(s, vocab_rows)
    dt = jnp.dtype(s.dtype)

    @jax.jit
    def make(key):
        names = sorted(top) + sorted(layer)
        keys = dict(zip(names, jax.random.split(key, len(names))))
        p = {n: _leaf(keys[n], *top[n], dt) for n in top}
        p["stages"] = [{"b0": {n: _leaf(keys[n], *layer[n], dt)
                               for n in layer}}]
        p["shared"] = None
        return p

    return make(jax.random.key(seed))


def _rms(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + scale)


def _rotary(x, theta):
    """x: (B, T, H, hd); rotate halves by position-dependent angles."""
    T, hd = x.shape[1], x.shape[-1]
    half = hd // 2
    inv = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = jnp.arange(T, dtype=F32)[:, None] * inv[None, :]      # (T, half)
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], -1)


@functools.partial(jax.jit, static_argnums=(2,))
def _layer(x, p, s):
    p = jax.tree.map(lambda w: w.astype(F32), p)
    T = x.shape[1]
    h = _rms(x, p["norm1"], s.norm_eps)
    q = _rotary(jnp.einsum("btd,dhk->bthk", h, p["wq"], precision=HI),
                s.rope_theta)
    k = _rotary(jnp.einsum("btd,dhk->bthk", h, p["wk"], precision=HI),
                s.rope_theta)
    v = jnp.einsum("btd,dhk->bthk", h, p["wv"], precision=HI)
    g = s.n_heads // s.n_kv_heads
    k = jnp.repeat(k, g, axis=2)
    v = jnp.repeat(v, g, axis=2)
    sc = jnp.einsum("bqhk,bshk->bhqs", q, k, precision=HI) \
        * s.head_dim ** -0.5
    causal = jnp.tril(jnp.ones((T, T), bool))
    sc = jnp.where(causal[None, None], sc, -jnp.inf)
    o = jnp.einsum("bhqs,bshk->bqhk", jax.nn.softmax(sc, axis=-1), v,
                   precision=HI)
    x = x + jnp.einsum("bqhk,hkd->bqd", o, p["wo"], precision=HI)
    h = _rms(x, p["norm2"], s.norm_eps)
    a = jax.nn.silu(jnp.einsum("btd,df->btf", h, p["w_gate"], precision=HI))
    u = jnp.einsum("btd,df->btf", h, p["w_up"], precision=HI)
    return x + jnp.einsum("btf,fd->btd", a * u, p["w_down"], precision=HI)


@functools.partial(jax.jit, static_argnums=(3,))
def _head(x, final_norm, head, s):
    x = _rms(x, final_norm.astype(F32), s.norm_eps)
    return jnp.einsum("btd,dv->btv", x, head[:, :s.vocab].astype(F32),
                      precision=HI)


def logits(params, s, tokens, cast=None):
    """(B, T) token ids -> (B, T, vocab) float32 logits, one layer at a
    time.  `cast`, where given, maps each weight matrix to the values a
    lower precision would hold (the control)."""
    cast = cast or (lambda w: w)
    emb = cast(params["embed"])
    x = jnp.take(emb, tokens, axis=0).astype(F32)
    stage = params["stages"][0]["b0"]
    for i in range(s.n_layers):
        layer = {n: (cast(w[i]) if w.ndim > 2 else w[i])
                 for n, w in stage.items()}
        x = _layer(x, layer, s)
    head = emb.T if s.tie else cast(params["lm_head"])
    return _head(x, params["final_norm"], head, s)
