"""Mamba2 mixer (SSD — state-space duality, arXiv:2405.21060).

Training/prefill uses the chunked SSD form: within-chunk quadratic
(attention-like) term + cross-chunk linear state recurrence scanned over
chunks; decode is the O(1) recurrent update.  Heads are sharded over tp;
the SSM state (B, nh, ns, hp) is tiny compared to a KV cache.

B and C come in `ssm_ngroups` groups of `ssm_state`; head h reads group
h // (heads / groups).  The depthwise conv runs over the concatenated
[x, B, C] stream (with a bias where `ssm_conv_bias`), and the gated
RMSNorm normalises each group's d_inner / groups channels on its own.
No (B, C) activation norm.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..distributed.sharding import DP, FSDP, TP, shard
from .common import F32, rms_norm


def init_mamba2(key, cfg, n_copies: int | None):
    d = cfg.d_model
    nh, hp, ns = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state
    di = nh * hp
    gn = cfg.ssm_ngroups * ns
    ks = jax.random.split(key, 8)
    dt = jnp.dtype(cfg.dtype)

    def mk(k, *shape, fan_in):
        full = shape if n_copies is None else (n_copies, *shape)
        return (jax.random.normal(k, full, F32) * fan_in ** -0.5).astype(dt)

    def full(val, *shape, dtype=F32):
        s = shape if n_copies is None else (n_copies, *shape)
        return jnp.full(s, val, dtype)

    p = {
        "norm": full(0.0, d, dtype=dt),
        "wx": mk(ks[0], d, di, fan_in=d),
        "wz": mk(ks[1], d, di, fan_in=d),
        "wB": mk(ks[2], d, gn, fan_in=d),
        "wC": mk(ks[3], d, gn, fan_in=d),
        "wdt": mk(ks[4], d, nh, fan_in=d),
        "conv_w": mk(ks[5], cfg.ssm_conv_dim, cfg.ssm_conv,
                     fan_in=cfg.ssm_conv),
        "A_log": full(0.0, nh),          # A = -exp(A_log) = -1
        "D": full(1.0, nh),
        "dt_bias": full(0.0, nh),
        "gated_norm": full(0.0, di, dtype=dt),
        "wout": mk(ks[6], di, d, fan_in=di),
    }
    if cfg.ssm_conv_bias:
        p["conv_b"] = full(0.0, cfg.ssm_conv_dim, dtype=dt)
    return p


def mamba2_specs(stacked: bool, conv_bias: bool = False):
    r = ("stack",) if stacked else ()
    s = {
        "norm": (*r, None), "wx": (*r, FSDP, TP), "wz": (*r, FSDP, TP),
        "wB": (*r, FSDP, None), "wC": (*r, FSDP, None),
        "wdt": (*r, FSDP, TP), "conv_w": (*r, TP, None),
        "A_log": (*r, TP), "D": (*r, TP), "dt_bias": (*r, TP),
        "gated_norm": (*r, TP), "wout": (*r, TP, FSDP),
    }
    if conv_bias:
        s["conv_b"] = (*r, TP)
    return s


def _proj(p, h, cfg):
    nh, hp, ns = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state
    x = jnp.einsum("...d,de->...e", h, p["wx"])
    z = jnp.einsum("...d,de->...e", h, p["wz"])
    Bm = jnp.einsum("...d,dn->...n", h, p["wB"])
    Cm = jnp.einsum("...d,dn->...n", h, p["wC"])
    dt = jax.nn.softplus(
        jnp.einsum("...d,dh->...h", h, p["wdt"]).astype(F32)
        + p["dt_bias"].astype(F32))
    return x, z, Bm, Cm, dt


def _causal_conv(stream, w, b=None):
    """Depthwise causal conv.  stream: (B, L, C); w: (C, K); b: (C,)."""
    B, L, C = stream.shape
    K = w.shape[-1]
    pad = jnp.pad(stream, ((0, 0), (K - 1, 0), (0, 0)))
    out = jax.lax.conv_general_dilated(
        pad.astype(F32), w.T[:, None, :].astype(F32),  # (K,1,C)->spec below
        window_strides=(1,), padding="VALID",
        dimension_numbers=("NWC", "WIO", "NWC"),
        feature_group_count=C)
    if b is not None:
        out = out + b.astype(F32)
    return jax.nn.silu(out).astype(stream.dtype)


def _heads(v, cfg):
    """B or C, (..., groups * ns), as the heads read it: (..., ns) shared
    by every head for one group, else (..., nh, ns) with head h reading
    group h // (nh / groups).  `_n(cfg)` is its einsum subscript."""
    g, ns = cfg.ssm_ngroups, cfg.ssm_state
    if g == 1:
        return v
    *lead, _ = v.shape
    v = jnp.broadcast_to(v.reshape(*lead, g, 1, ns),
                         (*lead, g, cfg.ssm_heads // g, ns))
    return v.reshape(*lead, cfg.ssm_heads, ns)


def _n(cfg) -> str:
    return "n" if cfg.ssm_ngroups == 1 else "hn"


def _gated_norm(y, scale, cfg):
    """RMSNorm of each group's d_inner / groups channels."""
    g = cfg.ssm_ngroups
    if g == 1:
        return rms_norm(y, scale, cfg.norm_eps)
    *lead, di = y.shape
    y = rms_norm(y.reshape(*lead, g, di // g), scale.reshape(g, di // g),
                 cfg.norm_eps)
    return y.reshape(*lead, di)


def mamba2_mixer(p, xin, cfg, extra=None):
    """Training/prefill forward.  xin: (B, L, d) -> (B, L, d), and the
    final SSM/conv state for cache hand-off.  `extra` joins the norm's
    input only, not the residual (zamba2's shared-block output)."""
    B, L, d = xin.shape
    nh, hp, ns = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state
    gn = cfg.ssm_ngroups * ns
    n_ = _n(cfg)
    Q = min(cfg.ssm_chunk, L)
    assert L % Q == 0, (L, Q)
    h = rms_norm(xin if extra is None else xin + extra, p["norm"],
                 cfg.norm_eps)
    x, z, Bm, Cm, dt = _proj(p, h, cfg)
    raw = jnp.concatenate([x, Bm, Cm], axis=-1)
    stream = _causal_conv(raw, p["conv_w"], p.get("conv_b"))
    di = nh * hp
    x, Bm, Cm = stream[..., :di], stream[..., di:di + gn], \
        stream[..., di + gn:]
    Bm, Cm = _heads(Bm, cfg), _heads(Cm, cfg)
    x = shard(x.reshape(B, L, nh, hp), DP, None, TP, None)
    A = -jnp.exp(p["A_log"].astype(F32))                   # (nh,)

    # chunked SSD: scan over chunks, quadratic only within a chunk
    nC = L // Q

    def chunks(a):
        return jnp.moveaxis(a.reshape(B, nC, Q, *a.shape[2:]), 1, 0)
    xc = chunks(x)                                  # (nC, B, Q, nh, hp)
    Bc = chunks(Bm.astype(F32))
    Cc = chunks(Cm.astype(F32))
    dtc = chunks(dt)                                              # f32
    causal = jnp.tril(jnp.ones((Q, Q), bool))

    def chunk_step(hstate, inp):
        xq, Bq, Cq, dtq = inp           # (B,Q,nh,hp),(B,Q,[nh,]ns)x2,(B,Q,nh)
        dA = dtq * A                                              # (B,Q,nh)
        La = jnp.cumsum(dA, axis=1)
        # intra-chunk quadratic term
        seg = La[:, :, None, :] - La[:, None, :, :]               # (B,Q,Q,nh)
        seg = shard(seg, DP, None, None, TP)
        M = jnp.where(causal[None, :, :, None], jnp.exp(seg), 0.0)
        if cfg.ssm_ngroups == 1:
            CB = jnp.einsum("bqn,bsn->bqs", Cq, Bq)[..., None]
        else:
            CB = jnp.einsum("bqhn,bshn->bqsh", Cq, Bq)
        W = CB * M * dtq[:, None, :, :]                           # (B,Q,S,nh)
        y_intra = jnp.einsum("bqsh,bshp->bqhp", W, xq.astype(F32))
        # inter-chunk: contribution of the carried state
        y_inter = jnp.einsum(f"bq{n_},bqh,bhnp->bqhp", Cq, jnp.exp(La),
                             hstate)
        # state update for the next chunk
        dBx_w = jnp.exp(La[:, -1, None, :] - La) * dtq            # (B,Q,nh)
        new_state = (hstate * jnp.exp(La[:, -1, :])[:, :, None, None]
                     + jnp.einsum(f"bq{n_},bqh,bqhp->bhnp", Bq, dBx_w,
                                  xq.astype(F32)))
        return new_state, y_intra + y_inter

    h0 = jnp.zeros((B, nh, ns, hp), F32)
    h_last, yc = jax.lax.scan(chunk_step, h0, (xc, Bc, Cc, dtc))
    y = jnp.moveaxis(yc, 0, 1).reshape(B, L, nh, hp)
    y = y + p["D"].astype(F32)[None, None, :, None] * x.astype(F32)
    y = y.reshape(B, L, di).astype(xin.dtype)
    y = y * jax.nn.silu(z.astype(F32)).astype(xin.dtype)    # gate
    y = _gated_norm(y, p["gated_norm"], cfg)
    out = jnp.einsum("bld,de->ble", y, p["wout"])
    # the decode step's conv state: the last K - 1 conv inputs
    conv_tail = raw[:, -(cfg.ssm_conv - 1):, :]
    return xin + out, (h_last, conv_tail.astype(xin.dtype))


def mamba2_step(p, xin, state, cfg, extra=None):
    """Decode step.  xin: (B, d); state = (ssm (B,nh,ns,hp) f32,
    conv (B, K-1, conv_dim)); `extra` as in `mamba2_mixer`."""
    ssm, conv = state
    B, d = xin.shape
    nh, hp, ns = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state
    di = nh * hp
    gn = cfg.ssm_ngroups * ns
    n_ = _n(cfg)
    h = rms_norm(xin if extra is None else xin + extra, p["norm"],
                 cfg.norm_eps)
    x, z, Bm, Cm, dt = _proj(p, h, cfg)
    new_col = jnp.concatenate([x, Bm, Cm], axis=-1)         # (B, conv_dim)
    win = jnp.concatenate([conv, new_col[:, None]], axis=1)  # (B,K,conv)
    conv_out = jnp.einsum("bkc,ck->bc", win.astype(F32),
                          p["conv_w"].astype(F32))
    if "conv_b" in p:
        conv_out = conv_out + p["conv_b"].astype(F32)
    conv_out = jax.nn.silu(conv_out)
    x = conv_out[:, :di].reshape(B, nh, hp)
    Bv = _heads(conv_out[:, di:di + gn], cfg)
    Cv = _heads(conv_out[:, di + gn:], cfg)
    A = -jnp.exp(p["A_log"].astype(F32))
    dec = jnp.exp(dt * A)                                   # (B, nh)
    ssm_new = (ssm * dec[:, :, None, None]
               + jnp.einsum(f"b{n_},bh,bhp->bhnp", Bv, dt, x))
    y = jnp.einsum(f"b{n_},bhnp->bhp", Cv, ssm_new)
    y = y + p["D"].astype(F32)[None, :, None] * x
    y = y.reshape(B, di).astype(xin.dtype)
    y = y * jax.nn.silu(z.astype(F32)).astype(xin.dtype)
    y = _gated_norm(y, p["gated_norm"], cfg)
    out = jnp.einsum("bd,de->be", y, p["wout"])
    return xin + out, (ssm_new, win[:, 1:].astype(conv.dtype))
