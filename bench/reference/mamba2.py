"""Plain reference of the Mamba2 (SSD) stack the mamba2-1.3b cells serve,
as the token-by-token recurrence of arXiv:2405.21060, in float32.

Per layer:
    h = rmsnorm(x) * (1 + norm)
    xs, z, B, C = h Wx, h Wz, h WB, h WC;  dt = softplus(h Wdt + dt_bias)
    [xs, B, C] = silu(causal depthwise conv of [xs, B, C], width d_conv)
    state_t = exp(dt_t A) state_{t-1} + B_t (dt_t xs_t)^T,  A = -exp(A_log)
    y_t = C_t state_t + D xs_t
    x = x + rmsnorm(y * silu(z)) * (1 + gated_norm)  Wout
then rmsnorm(x) * (1 + final_norm) and the tied output head.

Departures of the served model from the published layer, which this
reference shares because it checks the program as configured: no conv
bias, the residual kept in the served type, RMSNorm eps 1e-6 (the
configuration file's `departures`).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

F32 = jnp.float32
HI = jax.lax.Precision.HIGHEST


def layout(s, vocab_rows: int):
    """Leaf name -> (shape, init, dtype) in the serving program's layout;
    dtype None means the served type."""
    d, L, ns, nh, K = (s.d_model, s.n_layers, s.ssm_state, s.ssm_heads,
                       s.ssm_conv)
    di = s.d_inner
    layer = {
        "norm": ((L, d), ("norm",), None),
        "wx": ((L, d, di), ("normal", d), None),
        "wz": ((L, d, di), ("normal", d), None),
        "wB": ((L, d, ns), ("normal", d), None),
        "wC": ((L, d, ns), ("normal", d), None),
        "wdt": ((L, d, nh), ("normal", d), None),
        "conv_w": ((L, di + 2 * ns, K), ("normal", K), None),
        # Mamba2's own initialisation: A in [1, 16], dt in [1e-3, 1e-1]
        "A_log": ((L, nh), ("a_log",), F32),
        "D": ((L, nh), ("d_skip",), F32),
        "dt_bias": ((L, nh), ("dt_bias",), F32),
        "gated_norm": ((L, di), ("norm",), None),
        "wout": ((L, di, d), ("normal", di), None),
    }
    top = {"embed": ((vocab_rows, d), ("normal", d), None),
           "final_norm": ((d,), ("norm",), None)}
    return top, layer


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
    call, in the served type (the SSM's A, D and dt bias in float32, as
    served)."""
    top, layer = layout(s, vocab_rows)
    dt = jnp.dtype(s.dtype)

    @jax.jit
    def make(key):
        names = sorted(top) + sorted(layer)
        keys = dict(zip(names, jax.random.split(key, len(names))))
        p = {n: _leaf(keys[n], sh, init, dty or dt)
             for n, (sh, init, dty) in top.items()}
        p["stages"] = [{"b0": {n: _leaf(keys[n], sh, init, dty or dt)
                               for n, (sh, init, dty) in layer.items()}}]
        p["shared"] = None
        return p

    return make(jax.random.key(seed))


def _rms(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + scale)


@functools.partial(jax.jit, static_argnums=(2,))
def _layer(x, p, s):
    p = jax.tree.map(lambda w: w.astype(F32), p)
    B, T, _ = x.shape
    di, ns, nh, hp, K = (s.d_inner, s.ssm_state, s.ssm_heads,
                         s.ssm_head_dim, s.ssm_conv)
    h = _rms(x, p["norm"], s.norm_eps)
    xs = jnp.einsum("btd,de->bte", h, p["wx"], precision=HI)
    z = jnp.einsum("btd,de->bte", h, p["wz"], precision=HI)
    Bm = jnp.einsum("btd,dn->btn", h, p["wB"], precision=HI)
    Cm = jnp.einsum("btd,dn->btn", h, p["wC"], precision=HI)
    dt = jax.nn.softplus(jnp.einsum("btd,dh->bth", h, p["wdt"],
                                    precision=HI) + p["dt_bias"])
    cols = jnp.concatenate([xs, Bm, Cm], -1)                  # (B, T, C)
    padded = jnp.pad(cols, ((0, 0), (K - 1, 0), (0, 0)))
    conv = sum(padded[:, k:k + T] * p["conv_w"][:, k] for k in range(K))
    conv = jax.nn.silu(conv)
    xs = conv[..., :di].reshape(B, T, nh, hp)
    Bc, Cc = conv[..., di:di + ns], conv[..., di + ns:]
    A = -jnp.exp(p["A_log"])

    def step(state, inp):
        x_t, b_t, c_t, dt_t = inp            # (B,nh,hp) (B,ns) (B,ns) (B,nh)
        state = (state * jnp.exp(dt_t * A)[:, :, None, None]
                 + b_t[:, None, :, None] * (dt_t[:, :, None, None]
                                            * x_t[:, :, None, :]))
        y = jnp.einsum("bn,bhnp->bhp", c_t, state, precision=HI)
        return state, y + p["D"][None, :, None] * x_t

    seq = (jnp.moveaxis(xs, 1, 0), jnp.moveaxis(Bc, 1, 0),
           jnp.moveaxis(Cc, 1, 0), jnp.moveaxis(dt, 1, 0))
    _, ys = jax.lax.scan(step, jnp.zeros((B, nh, ns, hp), F32), seq)
    y = jnp.moveaxis(ys, 0, 1).reshape(B, T, di) * jax.nn.silu(z)
    y = _rms(y, p["gated_norm"], s.norm_eps)
    return x + jnp.einsum("bte,ed->btd", y, p["wout"], precision=HI)


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
    return _head(x, params["final_norm"], emb.T, s)
