"""Tiered MoE expert weights: hot experts resident in HBM.

qwen3-moe has 128 experts x ~29 MiB (bf16, d=4096, ff=1536, 3 mats)
per layer — 3.6 GiB/layer, 347 GiB total: far beyond HBM at small
serving footprints, with Zipf-skewed routing in production traces.
The RALT tracker scores experts by routed-token counts; swaps follow
the paper's pathways (retention of hot residents during eviction,
batch promotion of hot non-residents).  Unlike KV pages, expert
weights are immutable during serving => no version hazard.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..obs.serving import DEFAULT_SERVING_OBS
from .hotness import HotTracker, TrackerConfig
from .kvcache import HBM_BW, PCIE_BW, SimClock


class ExpertCache:
    # Obs plane: records while a profiler session does (repro.obs.serving).
    _obs = DEFAULT_SERVING_OBS
    _obs_track = "expert"

    def __init__(self, expert_weights: np.ndarray, fast_experts: int,
                 swap_every: int = 16):
        """expert_weights: host array (E, ...) — one blob per expert."""
        self.host = expert_weights
        E = expert_weights.shape[0]
        self.E = E
        self.fast_experts = fast_experts
        self.blob_bytes = int(expert_weights[0].nbytes)
        self.cache = jnp.zeros((fast_experts, *expert_weights.shape[1:]),
                               expert_weights.dtype)
        self.slot_of = np.full(E, -1, np.int64)
        self.expert_of_slot = np.full(fast_experts, -1, np.int64)
        self.free = list(range(fast_experts))[::-1]
        self.tracker = HotTracker(TrackerConfig(
            n_units=E, unit_bytes=self.blob_bytes,
            fast_bytes=fast_experts * self.blob_bytes))
        self.tracker._obs_track = self._obs_track
        self.clock = SimClock()
        self.swap_every = swap_every
        self._steps = 0

    def route(self, expert_counts: np.ndarray):
        """Record one step's router histogram (E,) and fetch weights.
        Resident experts are HBM reads; non-resident experts are
        streamed from host (PCIe) for this step and staged."""
        obs, c = self._obs, self.clock
        rec = obs.poll()
        if rec:
            t0 = c.total_s
            s0, m0 = c.slow_hits, c.sweeps
        used = np.nonzero(expert_counts > 0)[0]
        hits = jnp.zeros(self.E, bool).at[jnp.asarray(used)].set(True)
        self.tracker.record(hits)
        for e in used:
            if self.slot_of[e] >= 0:
                self.clock.hbm_s += self.blob_bytes / HBM_BW
                self.clock.fast_hits += 1
            else:
                self.clock.pcie_s += self.blob_bytes / PCIE_BW
                self.clock.slow_hits += 1
        self._steps += 1
        if self._steps % self.swap_every == 0:
            self.rebalance()
        if rec and obs.attribution:
            obs.attr.observe("expert", c.total_s - t0, len(used),
                             c.slow_hits - s0, c.sweeps > m0)

    def rebalance(self):
        """Sweep: retain hot residents, demote cold ones, promote the
        hottest non-residents into freed slots."""
        obs, c = self._obs, self.clock
        if obs.enabled:
            obs.tracer.begin(
                self._obs_track, "expert/rebalance",
                {"resident": int((self.expert_of_slot >= 0).sum())})
            r0, d0, p0 = c.retained, c.demoted, c.promoted
        self.tracker.refresh_limits()
        scores = np.asarray(self.tracker.scores())
        hot = self.tracker.hot()
        order = np.argsort(-scores)
        want = [int(e) for e in order[:self.fast_experts] if hot[e]]
        want_set = set(want)
        for s, e in enumerate(self.expert_of_slot):
            if e >= 0 and e not in want_set:
                self.slot_of[e] = -1
                self.expert_of_slot[s] = -1
                self.free.append(int(s))
                self.clock.demoted += 1
            elif e >= 0:
                self.clock.retained += 1
        new = [e for e in want if self.slot_of[e] < 0]
        slots = []
        for e in new:
            if not self.free:
                break
            s = self.free.pop()
            slots.append(s)
            self.slot_of[e] = s
            self.expert_of_slot[s] = e
        if slots:
            self.cache = self.cache.at[jnp.asarray(slots)].set(
                jnp.asarray(self.host[new[:len(slots)]]))
            self.clock.pcie_s += len(slots) * self.blob_bytes / PCIE_BW
            self.clock.promoted += len(slots)
        c.sweeps += 1
        if obs.enabled:
            tr, track = obs.tracer, self._obs_track
            if c.retained > r0:                       # retention pathway
                tr.instant(track, "page/retained",
                           {"pages": c.retained - r0})
            if c.promoted > p0:                       # promo-by-compaction
                tr.instant(track, "page/promo_compaction",
                           {"pages": c.promoted - p0})
            tr.end(track, "expert/rebalance",
                   {"demoted": c.demoted - d0,
                    "promoted": c.promoted - p0})
            obs.sample(self, track)

    def __getstate__(self):
        state = dict(self.__dict__)
        state.pop("_obs", None)
        state.pop("_obs_track", None)
        return state

    def resident_fraction(self, expert_counts: np.ndarray) -> float:
        """Fraction of routed tokens whose expert is HBM-resident."""
        total = expert_counts.sum()
        if total == 0:
            return 0.0
        res = sum(int(c) for e, c in enumerate(expert_counts)
                  if self.slot_of[e] >= 0)
        return res / float(total)
