"""Batched serving engine: prefill + decode over the decode cache.

A deliberately simple continuous-batching core: fixed decode batch B,
requests occupy slots; prefill runs per-request (teacher-forced decode
into the slot's cache rows — exact, reuses the decode step so the
engine needs only one compiled function per batch size); decode steps
advance every live slot one token.  The tiered-KV/embedding paths from
`repro.tiering` hook in at the cache-fetch boundary and are exercised
by `benchmarks/tiered_serving.py` at the page level.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from ..models.transformer import decode_step, init_cache, init_params
from ..obs.serving import DEFAULT_SERVING_OBS

# The parameters are an argument, never closed over: JAX embeds a
# closed-over array in the program as a constant (gigabytes at published
# widths).  The cache is donated, so each step and each wave's reset
# update it in place instead of holding a second copy.
_decode = jax.jit(decode_step, static_argnums=1, donate_argnums=2)
_zeroed = jax.jit(lambda cache: jax.tree.map(jnp.zeros_like, cache),
                  donate_argnums=0, keep_unused=True)


@dataclasses.dataclass
class Request:
    rid: int
    prompt: list
    max_new: int
    out: list = dataclasses.field(default_factory=list)
    done: bool = False


class ServeEngine:
    # Obs plane: records while a profiler session does (repro.obs.serving).
    _obs = DEFAULT_SERVING_OBS
    _obs_track = "engine"

    def __init__(self, cfg, params=None, *, batch: int = 8,
                 max_len: int = 512, seed: int = 0):
        self.cfg = cfg
        self.batch = batch
        self.max_len = max_len
        self.params = params if params is not None else init_params(
            jax.random.key(seed), cfg)
        self.cache = init_cache(cfg, batch, max_len)
        self.slots: list = [None] * batch
        self.pos = 0                    # shared position (lockstep)
        self.queue: list = []
        self.completed: list = []
        self.steps_used = 0
        self.waves = 0                  # waves run over the engine's life
        self.starved = False            # budget expired with live work

    def submit(self, req: Request):
        self.queue.append(req)

    @property
    def requests_completed(self) -> int:
        return len(self.completed)

    def _assign(self) -> int:
        """Fill free slots from the queue.  The wave's gate: the plane is
        asked here, after the pops, whether this wave records."""
        admitted = []
        for i in range(self.batch):
            if self.slots[i] is None and self.queue:
                self.slots[i] = self.queue.pop(0)
                admitted.append(self.slots[i].rid)
        obs = self._obs
        if obs.poll():
            for rid in admitted:
                obs.tracer.instant(self._obs_track, "engine/admit",
                                   {"rid": rid})
        return len(admitted)

    def __getstate__(self):
        state = dict(self.__dict__)
        state.pop("_obs", None)
        state.pop("_obs_track", None)
        return state

    def reset(self):
        """Zero the decode cache in place (start of a wave)."""
        self.cache = _zeroed(self.cache)

    def step(self, tokens, pos):
        """One decode step for every slot: tokens (batch,) int32 at the
        shared position `pos`.  Returns the (batch, padded vocab)
        logits."""
        logits, self.cache = _decode(self.params, self.cfg, self.cache,
                                     jnp.asarray(tokens, jnp.int32),
                                     jnp.int32(pos))
        return logits

    def run(self, max_steps: int = 10_000):
        """Lockstep loop: all live slots share the position counter
        (simplification: prompts are left-aligned per generation wave;
        a production engine would use per-slot positions).

        The step budget is no longer silent: `steps_used` counts the
        decode-step invocations, and when `max_steps` expires with live
        slots or queued requests the engine sets `starved`, emits a
        traced `engine/starved` instant, and returns what completed."""
        obs, track = self._obs, self._obs_track
        tr = obs.tracer
        self.steps_used = 0
        self.starved = False
        while (self.queue or any(self.slots)) and max_steps:
            assigned = self._assign()
            live = [r for r in self.slots if r is not None]
            if not live:
                break
            wave = obs.enabled          # the answer _assign's poll gave
            if wave:
                if assigned:
                    tr.instant(track, "engine/assign",
                               {"assigned": assigned,
                                "queued": len(self.queue)})
                tr.begin(track, "engine/reset")
            wave_prompt = max(len(r.prompt) for r in live)
            wave_new = max(r.max_new for r in live)
            self.reset()
            toks = np.zeros((self.batch,), np.int32)
            if wave:
                tr.swap(track, "engine/prefill",
                        {"live": len(live), "prompt_len": wave_prompt})
            # teacher-forced prefill (exact; shares the decode step)
            for t in range(wave_prompt + wave_new):
                if wave and t == wave_prompt:
                    tr.swap(track, "engine/decode",
                            {"live": len(live), "max_new": wave_new})
                rec = obs.poll()
                if rec:
                    tr.begin(track, "engine/step",
                             {"wave": self.waves, "step": t})
                    tr.begin(track, "engine/feed")
                for i, r in enumerate(self.slots):
                    if r is None:
                        continue
                    if t < len(r.prompt):
                        toks[i] = r.prompt[t]
                    elif r.out and not r.done:
                        toks[i] = r.out[-1]
                if rec:
                    tr.swap(track, "engine/dispatch")
                logits = self.step(toks, t)
                # logits cover the padded vocab: never emit a pad id
                nxt = jnp.argmax(logits[:, :self.cfg.vocab], axis=-1)
                if rec:
                    tr.swap(track, "engine/sync")
                nxt = np.asarray(nxt)
                if rec:
                    tr.swap(track, "engine/emit")
                for i, r in enumerate(self.slots):
                    if r is None or r.done:
                        continue
                    if t >= len(r.prompt) - 1:
                        if rec and not r.out:
                            tr.instant(track, "engine/first_token",
                                       {"rid": r.rid})
                        r.out.append(int(nxt[i]))
                        if len(r.out) >= r.max_new:
                            r.done = True
                if rec:
                    tr.end(track, "engine/emit")
                    tr.end(track, "engine/step")
                max_steps -= 1
                self.steps_used += 1
                if max_steps <= 0:
                    break
            if wave:
                tr.end(track)   # close prefill OR decode span
            self.waves += 1
            for i, r in enumerate(self.slots):
                if r is not None and r.done:
                    self.completed.append(r)
                    self.slots[i] = None
        if max_steps <= 0 and (self.queue or any(self.slots)):
            self.starved = True
            if obs.enabled:
                obs.tracer.instant(
                    track, "engine/starved",
                    {"steps_used": self.steps_used,
                     "live_slots": sum(r is not None
                                       for r in self.slots),
                     "queued": len(self.queue),
                     "completed": len(self.completed)})
        if obs.enabled:
            obs.tracer.counter(track, "engine",
                               {"steps_used": self.steps_used,
                                "completed": len(self.completed)})
        return self.completed
