"""Plain reference of the Zamba2 stack the zamba2 cells serve, in float32
at the highest matmul precision, one layer at a time.

Every Mamba2 layer l, with t the output of the shared-block use that
precedes it (at the `hybrid_layer_ids`; t = 0 elsewhere):
    h = rmsnorm(x + t) * (1 + norm)            # the residual stays x
    xs, z = h Wx, h Wz;  B, C = h WB, h WC (groups x d_state each)
    dt = softplus(h Wdt + dt_bias)
    [xs, B, C] = silu(causal depthwise conv of [xs, B, C] + conv_b)
    head j reads group j // (heads / groups) of B and C
    state_t = exp(dt_t A) state_{t-1} + B_t (dt_t xs_t)^T,  A = -exp(A_log)
    y_t = C_t state_t + D xs_t
    y = y * silu(z), then rmsnorm over each group's d_inner / groups
        channels * (1 + gated_norm)
    x = x + y Wout
Use u, at depth hybrid_layer_ids[u], runs shared block b = u mod
num_mem_blocks on x and x0, the embedding output (transformers'
modeling_zamba2.py, Zamba2HybridLayer and Zamba2AttentionDecoderLayer):
    a = rmsnorm(concat(x, x0)) * (1 + norm1)   # 2 d wide
    q, k, v = a Wq_b, a Wk_b, a Wv_b;  rotary on q and k over all of
        head_dim (halves rotated, frequencies theta^(-i / (hd/2)))
    o = softmax(q k^T (head_dim / 2)^-0.5, causal) v Wo_b   # no residual
    f = rmsnorm(o) * (1 + norm2)
    [g, u] = f Wgu_b + (f A_u) B_u             # rank-r adapter of use u
    t = (gelu(g) * u) Wdown_b Wlin_u           # exact (erf) GELU
then rmsnorm(x) * (1 + final_norm) and the tied output head (the
embedding).  Every norm has eps `rms_norm_eps`.

Departures from the published model: none.  Norm scales are stored as
s with the weight 1 + s (the same function, a change of variable), and
the in_proj's rows as separate Wx, Wz, WB, WC, Wdt matrices.  The weights
come from the seed in the serving program's layout: its stages group the
layers as `bench/families/zamba2.py` `stage_layout` says, which this
module reads only to place the weights (`bench/drivers/serve.py` checks
the tree against the program's).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from bench.lib.cell import load_module

F32 = jnp.float32
HI = jax.lax.Precision.HIGHEST


def _family():
    return load_module("families", "zamba2")


def _mamba_leaves(s, r):
    d, di, nh, K = s.d_model, s.d_inner, s.ssm_heads, s.ssm_conv
    gn = s.ssm_ngroups * s.ssm_state
    leaves = {
        "norm": ((r, d), ("norm",), None),
        "wx": ((r, d, di), ("normal", d), None),
        "wz": ((r, d, di), ("normal", d), None),
        "wB": ((r, d, gn), ("normal", d), None),
        "wC": ((r, d, gn), ("normal", d), None),
        "wdt": ((r, d, nh), ("normal", d), None),
        "conv_w": ((r, s.conv_dim, K), ("normal", K), None),
        # Mamba2's own initialisation: A in [1, 16], dt in [1e-3, 1e-1]
        "A_log": ((r, nh), ("a_log",), F32),
        "D": ((r, nh), ("d_skip",), F32),
        "dt_bias": ((r, nh), ("dt_bias",), F32),
        "gated_norm": ((r, di), ("norm",), None),
        "wout": ((r, di, d), ("normal", di), None),
    }
    if s.conv_bias:
        leaves["conv_b"] = ((r, s.conv_dim), ("normal", K), None)
    return leaves


def _use_leaves(s, r):
    d, rank, ff = s.d_model, s.adapter_rank, s.d_ff
    return {"lin": ((r, d, d), ("normal", d), None),
            "ad_a": ((r, d, rank), ("normal", d), None),
            "ad_b": ((r, rank, 2 * ff), ("normal", rank), None)}


def _block_leaves(s):
    d, H, KV, hd, ff = (s.d_model, s.n_heads, s.n_kv_heads, s.head_dim,
                        s.d_ff)
    return {"norm1": ((2 * d,), ("norm",), None),
            "wq": ((2 * d, H, hd), ("normal", 2 * d), None),
            "wk": ((2 * d, KV, hd), ("normal", 2 * d), None),
            "wv": ((2 * d, KV, hd), ("normal", 2 * d), None),
            "wo": ((H, hd, d), ("normal", H * hd), None),
            "norm2": ((d,), ("norm",), None),
            "w_gu": ((d, 2 * ff), ("normal", d), None),
            "w_down": ((ff, d), ("normal", ff), None)}


def layout(s, vocab_rows: int) -> dict:
    """The serving program's weight tree, each leaf (shape, init, dtype);
    dtype None means the served type."""
    stages = []
    for repeat, items in _family().stage_layout(s):
        stages.append({f"b{i}": (_mamba_leaves(s, repeat) if it == "m"
                                 else _use_leaves(s, repeat))
                       for i, it in enumerate(items)})
    return {"embed": ((vocab_rows, s.d_model), ("normal", s.d_model), None),
            "final_norm": ((s.d_model,), ("norm",), None),
            "stages": stages,
            "shared": [_block_leaves(s) for _ in range(s.n_mem_blocks)]}


def _is_leaf(x):
    return isinstance(x, tuple) and len(x) == 3 and isinstance(x[1], tuple)


def _leaf(key, shape, init, dtype):
    kind = init[0]
    u = jax.random.uniform(key, shape, F32)
    if kind == "norm":
        v = 0.1 * jax.random.normal(key, shape, F32)
    elif kind == "normal":
        v = jax.random.normal(key, shape, F32) * init[1] ** -0.5
    elif kind == "a_log":
        v = jnp.log(1.0 + 15.0 * u)
    elif kind == "d_skip":
        v = 0.5 + u
    elif kind == "dt_bias":
        dt = jnp.exp(math.log(1e-3) + u * (math.log(1e-1) - math.log(1e-3)))
        v = dt + jnp.log(-jnp.expm1(-dt))       # softplus^-1(dt)
    else:
        raise ValueError(kind)
    return v.astype(dtype)


def make_params(s, seed: int, vocab_rows: int):
    """The served weights from the seed, made on the device in one jitted
    call, in the served type (the SSM's A, D and dt bias in float32)."""
    tree = layout(s, vocab_rows)
    leaves, treedef = jax.tree.flatten(tree, is_leaf=_is_leaf)
    dt = jnp.dtype(s.dtype)

    @jax.jit
    def make(key):
        keys = jax.random.split(key, len(leaves))
        return treedef.unflatten(
            [_leaf(k, sh, init, dty or dt)
             for k, (sh, init, dty) in zip(keys, leaves)])

    return make(jax.random.key(seed))


def _rms(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + scale)


@functools.partial(jax.jit, static_argnums=(3,))
def _mamba(x, t, p, s):
    p = jax.tree.map(lambda w: w.astype(F32), p)
    B, T, _ = x.shape
    di, ns, nh, hp, K, g = (s.d_inner, s.ssm_state, s.ssm_heads,
                            s.ssm_head_dim, s.ssm_conv, s.ssm_ngroups)
    h = _rms(x + t, p["norm"], s.norm_eps)
    xs = jnp.einsum("btd,de->bte", h, p["wx"], precision=HI)
    z = jnp.einsum("btd,de->bte", h, p["wz"], precision=HI)
    Bm = jnp.einsum("btd,dn->btn", h, p["wB"], precision=HI)
    Cm = jnp.einsum("btd,dn->btn", h, p["wC"], precision=HI)
    dt = jax.nn.softplus(jnp.einsum("btd,dh->bth", h, p["wdt"],
                                    precision=HI) + p["dt_bias"])
    cols = jnp.concatenate([xs, Bm, Cm], -1)                  # (B, T, C)
    padded = jnp.pad(cols, ((0, 0), (K - 1, 0), (0, 0)))
    conv = sum(padded[:, k:k + T] * p["conv_w"][:, k] for k in range(K))
    conv = jax.nn.silu(conv + p.get("conv_b", 0.0))
    xs = conv[..., :di].reshape(B, T, nh, hp)
    head_group = jnp.arange(nh) // (nh // g)
    Bh = conv[..., di:di + g * ns].reshape(B, T, g, ns)[:, :, head_group]
    Ch = conv[..., di + g * ns:].reshape(B, T, g, ns)[:, :, head_group]
    A = -jnp.exp(p["A_log"])

    def step(state, inp):
        x_t, b_t, c_t, dt_t = inp      # (B,nh,hp) (B,nh,ns) x2 (B,nh)
        state = (state * jnp.exp(dt_t * A)[:, :, None, None]
                 + b_t[:, :, :, None] * (dt_t[:, :, None, None]
                                         * x_t[:, :, None, :]))
        y = jnp.einsum("bhn,bhnp->bhp", c_t, state, precision=HI)
        return state, y + p["D"][None, :, None] * x_t

    seq = (jnp.moveaxis(xs, 1, 0), jnp.moveaxis(Bh, 1, 0),
           jnp.moveaxis(Ch, 1, 0), jnp.moveaxis(dt, 1, 0))
    _, ys = jax.lax.scan(step, jnp.zeros((B, nh, ns, hp), F32), seq)
    y = jnp.moveaxis(ys, 0, 1).reshape(B, T, di) * jax.nn.silu(z)
    y = _rms(y.reshape(B, T, g, di // g),
             p["gated_norm"].reshape(g, di // g), s.norm_eps)
    return x + jnp.einsum("bte,ed->btd", y.reshape(B, T, di), p["wout"],
                          precision=HI)


def _rotary(x, theta):
    """x: (B, T, H, hd); rotate halves by position-dependent angles."""
    T, hd = x.shape[1], x.shape[-1]
    half = hd // 2
    inv = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = jnp.arange(T, dtype=F32)[:, None] * inv[None, :]      # (T, half)
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], -1)


@functools.partial(jax.jit, static_argnums=(4,))
def _shared(x, x0, blk, use, s):
    blk = jax.tree.map(lambda w: w.astype(F32), blk)
    use = jax.tree.map(lambda w: w.astype(F32), use)
    T = x.shape[1]
    a = _rms(jnp.concatenate([x, x0], -1), blk["norm1"], s.norm_eps)
    q = _rotary(jnp.einsum("btd,dhk->bthk", a, blk["wq"], precision=HI),
                s.rope_theta)
    k = _rotary(jnp.einsum("btd,dhk->bthk", a, blk["wk"], precision=HI),
                s.rope_theta)
    v = jnp.einsum("btd,dhk->bthk", a, blk["wv"], precision=HI)
    rep = s.n_heads // s.n_kv_heads
    k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
    sc = jnp.einsum("bqhk,bshk->bhqs", q, k, precision=HI) * s.attn_scale
    causal = jnp.tril(jnp.ones((T, T), bool))
    sc = jnp.where(causal[None, None], sc, -jnp.inf)
    o = jnp.einsum("bhqs,bshk->bqhk", jax.nn.softmax(sc, axis=-1), v,
                   precision=HI)
    o = jnp.einsum("bqhk,hkd->bqd", o, blk["wo"], precision=HI)
    f = _rms(o, blk["norm2"], s.norm_eps)
    gu = jnp.einsum("btd,df->btf", f, blk["w_gu"], precision=HI) \
        + jnp.einsum("btr,rf->btf", jnp.einsum("btd,dr->btr", f, use["ad_a"],
                                               precision=HI),
                     use["ad_b"], precision=HI)
    gate, up = gu[..., :s.d_ff], gu[..., s.d_ff:]
    m = jax.nn.gelu(gate, approximate=False) * up
    m = jnp.einsum("btf,fd->btd", m, blk["w_down"], precision=HI)
    return jnp.einsum("btd,de->bte", m, use["lin"], precision=HI)


@functools.partial(jax.jit, static_argnums=(3,))
def _head(x, final_norm, head, s):
    x = _rms(x, final_norm.astype(F32), s.norm_eps)
    return jnp.einsum("btd,dv->btv", x, head[:, :s.vocab].astype(F32),
                      precision=HI)


def _slot(params, si, bi, r):
    return {n: w[r] for n, w in params["stages"][si][f"b{bi}"].items()}


def logits(params, s, tokens, cast=None):
    """(B, T) token ids -> (B, T, vocab) float32 logits, one layer at a
    time.  `cast`, where given, maps each weight matrix to the values a
    lower precision would hold (the control)."""
    cast = cast or (lambda w: w)

    def matrices(p):
        return {n: (cast(w) if w.ndim >= 2 else w) for n, w in p.items()}

    emb = cast(params["embed"])
    x0 = jnp.take(emb, tokens, axis=0).astype(F32)
    x, t = x0, jnp.zeros_like(x0)
    use = 0
    for si, (repeat, items) in enumerate(_family().stage_layout(s)):
        for r in range(repeat):
            for bi, it in enumerate(items):
                if it == "m":
                    x = _mamba(x, t, matrices(_slot(params, si, bi, r)), s)
                    t = jnp.zeros_like(x)
                    continue
                block = use % s.n_mem_blocks
                assert it == ("s", block), (it, use)
                t = _shared(x, x0, matrices(params["shared"][block]),
                            matrices(_slot(params, si, bi, r)), s)
                use += 1
    return _head(x, params["final_norm"], emb.T, s)
