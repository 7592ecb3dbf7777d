"""RALT-in-JAX: the paper's hotness tracker over dense unit ids.

The tracked units on TPU (KV pages, experts, vocab rows) are dense
integers, so RALT's on-disk LSM becomes a fixed-capacity on-device
score table — but the *algorithms* are the paper's, unchanged:

  * exponential-smoothing scores with lazy decay:
    real_score(now) = alpha^(now - tick) * score   (§3.2), updated by
    the fused Pallas kernel `kernels.ops.ralt_update`;
  * time slices advance every `gamma x fast-tier bytes` accessed (§3.2);
  * eviction / hot-threshold via the paper's *sampling* scheme: sample
    positions uniformly in cumulative-size space, take the k-th largest
    sampled score (§3.2 Fig. 4);
  * auto-tuning of the hot-set size limit via Algorithm 1: counters c
    (+delta_c per hit, capped c_max, -1 per R bytes accessed) and
    stability tags t; limit = clamp(stable_size + D_hs, [L_hs, R_hs]).

Everything is jit-compatible (fixed shapes); the host only reads back
scalars (hot set size, limits) for orchestration decisions.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from ..kernels import ops as kops

F32 = jnp.float32


@dataclasses.dataclass(frozen=True)
class TrackerConfig:
    n_units: int                  # tracked units (pages/experts/rows)
    unit_bytes: int               # HotRAP size of one unit
    fast_bytes: int               # fast-tier capacity in bytes
    alpha: float = 0.999
    gamma: float = 0.001          # time slice per gamma*fast_bytes
    # Algorithm 1
    delta_c: float = 2.6
    c_max: float = 5.0
    hot_lo_frac: float = 0.05     # L_hs / fast_bytes
    hot_hi_frac: float = 0.70     # R_hs
    d_hs_frac: float = 0.10       # D_hs / R_hs
    init_hot_frac: float = 0.50
    n_samples: int = 256          # sampling-based threshold (§3.2)


def init_state(cfg: TrackerConfig) -> dict:
    n = cfg.n_units
    return {
        "tick": jnp.zeros(n, jnp.int32),
        "score": jnp.zeros(n, F32),
        "c": jnp.zeros(n, F32),               # Alg. 1 counter
        "t": jnp.zeros(n, jnp.bool_),         # Alg. 1 stability tag
        "seen": jnp.zeros(n, jnp.bool_),
        "now": jnp.zeros((), jnp.int32),
        "accessed_bytes": jnp.zeros((), F32),     # since last slice
        "accessed_bytes_r": jnp.zeros((), F32),   # since last decrement
        "hot_limit": jnp.asarray(
            cfg.init_hot_frac * cfg.fast_bytes, F32),
        "threshold": jnp.zeros((), F32),
    }


def _slice_every(cfg):
    return cfg.gamma * cfg.fast_bytes


def record_accesses(state, hit_mask, cfg: TrackerConfig):
    """Log one batch of accesses (bool mask over units).  Advances the
    time slice when gamma*fast_bytes have been accessed, applies the
    fused decay+hit kernel, and runs Alg. 1's counter updates."""
    batch_bytes = hit_mask.sum().astype(F32) * cfg.unit_bytes
    acc = state["accessed_bytes"] + batch_bytes
    adv = (acc // _slice_every(cfg)).astype(jnp.int32)
    now = state["now"] + adv
    acc = acc - adv.astype(F32) * _slice_every(cfg)

    new_tick, new_score, _ = kops.ralt_update(
        state["tick"], state["score"], hit_mask, now,
        state["threshold"], alpha=cfg.alpha)

    # Algorithm 1 counters
    c = jnp.where(hit_mask,
                  jnp.minimum(state["c"] + cfg.delta_c, cfg.c_max),
                  state["c"])
    t = jnp.where(hit_mask & state["seen"], True, state["t"])
    seen = state["seen"] | hit_mask

    # decrement sweep every R bytes accessed
    R = cfg.hot_hi_frac * cfg.fast_bytes
    accr = state["accessed_bytes_r"] + batch_bytes
    dec = (accr // R).astype(F32)
    accr = accr - dec * R
    c = jnp.maximum(c - dec, 0.0)
    t = t & (c > 0)

    return {**state, "tick": new_tick, "score": new_score, "c": c,
            "t": t, "seen": seen, "now": now, "accessed_bytes": acc,
            "accessed_bytes_r": accr}


def current_scores(state, cfg: TrackerConfig):
    """Lazily-decayed scores at `now` (§3.2 real_score)."""
    dt = (state["now"] - state["tick"]).astype(F32)
    return state["score"] * jnp.power(jnp.asarray(cfg.alpha, F32), dt)


def sampled_threshold(state, cfg: TrackerConfig, target_bytes):
    """The paper's eviction-threshold sampling (§3.2, Fig. 4).

    Sample n positions uniformly in cumulative-size space (uniform unit
    sizes => uniform unit ids), take the k-th largest sampled score
    where k = n * target_bytes / total_bytes."""
    scores = current_scores(state, cfg)
    n = cfg.n_samples
    key = jax.random.fold_in(jax.random.key(17), state["now"])
    idx = jax.random.randint(key, (n,), 0, cfg.n_units)
    samp = jnp.sort(scores[idx])[::-1]            # descending
    total = float(cfg.n_units * cfg.unit_bytes)    # may exceed int32
    k = jnp.clip((n * target_bytes / total).astype(jnp.int32),
                 0, n - 1)
    return samp[k]


def update_limits(state, cfg: TrackerConfig):
    """Alg. 1 lines 18–21: hot-set limit from the stable-record size;
    refresh the hot threshold from the sampled quantile."""
    stable = (state["c"] > 0) & state["t"]
    stable_bytes = stable.sum().astype(F32) * cfg.unit_bytes
    L = cfg.hot_lo_frac * cfg.fast_bytes
    Rl = cfg.hot_hi_frac * cfg.fast_bytes
    D = cfg.d_hs_frac * Rl
    hot_limit = jnp.maximum(L, jnp.minimum(stable_bytes + D, Rl))
    threshold = sampled_threshold(state, cfg, hot_limit)
    return {**state, "hot_limit": hot_limit, "threshold": threshold}


def hot_mask(state, cfg: TrackerConfig):
    """Units currently above the hot threshold (bounded by hot_limit
    through the threshold construction)."""
    return current_scores(state, cfg) >= jnp.maximum(state["threshold"],
                                                     1e-6)


class HotTracker:
    """Convenience stateful wrapper (jitted pure ops inside)."""

    def __init__(self, cfg: TrackerConfig):
        self.cfg = cfg
        self.state = init_state(cfg)
        self._build_jits()

    def _build_jits(self):
        cfg = self.cfg
        self._record = jax.jit(
            lambda s, m: record_accesses(s, m, cfg))
        self._limits = jax.jit(lambda s: update_limits(s, cfg))
        self._hot = jax.jit(lambda s: hot_mask(s, cfg))

    def __getstate__(self):
        """Jitted closures don't pickle; rebuild them on load."""
        state = dict(self.__dict__)
        for k in ("_record", "_limits", "_hot"):
            state.pop(k, None)
        return state

    def __setstate__(self, state):
        self.__dict__.update(state)
        self._build_jits()

    def record(self, hit_mask):
        self.state = self._record(self.state, hit_mask)

    def record_ids(self, ids):
        mask = jnp.zeros(self.cfg.n_units, bool).at[ids].set(True)
        self.record(mask)

    def refresh_limits(self):
        self.state = self._limits(self.state)

    def hot(self):
        return self._hot(self.state)

    def scores(self):
        return current_scores(self.state, self.cfg)
