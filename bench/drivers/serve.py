"""Serving driver: `ServeEngine.run` over requests the benchmark submits.

The engine is the one `repro.launch.serve.serve` builds, with the
benchmark's weights.  Token times are taken without touching the
program: each request's `out` is a list whose `append` records the host
clock, and the engine appends each token right after its host sync.

Two arrival kinds (the traffic file's `arrival`):
  closed   the queue never runs dry; the window closes at `--seconds`,
           the wave in progress runs to its end and counts, so the window
           holds whole waves;
  gamma    open loop: a feeder thread submits each request at its due
           time whatever the engine is doing, until `--seconds`; then
           the engine serves every request already due (the drain), so
           each gets a real first-token time.  The drain is cut
           `drain_s` after the close (default 60 s); a request still
           queued then counts as missing.

A traced run's stretch starts at a wave's first `pop`.  It stops at the
first `pop` of a later wave, or while the engine is idle, so it holds
whole waves and the idle time between them.
"""
from __future__ import annotations

import threading
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench.lib.cell import load_module
from bench.reference import lowp

RUN_SPAN = "bench/engine_run"
WAIT_SPAN = "bench/wait_arrival"
WINDOW_SPAN = "bench/window"
DRAIN_S = 60.0              # the drain is cut this long after the close
IDLE_POLL_S = 0.01          # how often an idle engine polls the stretch
# Requests the check compares with the reference: the longest finished
# one and others drawn from the seed, up to this many served tokens.
CHECK_TOKENS = 384
CHECK_MAX_REQUESTS = 8


class TimedTokens(list):
    """A request's output list; `append` records when each token came."""

    def __init__(self):
        super().__init__()
        self.times: list = []

    def append(self, tok):
        self.times.append(time.perf_counter())
        super().append(tok)


class GatedQueue(list):
    """The engine's queue.  With a `supply` it never runs dry (a closed
    loop).  Once `closed`, the engine sees it empty: it finishes the wave
    in progress and returns.

    The engine pops a wave's requests one after another before its first
    step, so a pop with a new `wave_key()` (the engine's step count, and
    which `run` call it is in) starts a wave: `on_wave` is called there
    and each request keeps the wave's number."""

    def __init__(self, supply=None, wave_key=None, on_wave=None):
        super().__init__()
        self.supply = supply
        self.closed = False
        self.wave_key, self.on_wave = wave_key, on_wave
        self.key, self.wave = None, -1

    def __bool__(self):
        return not self.closed and (self.supply is not None
                                    or list.__len__(self) > 0)

    def pop(self, i=-1):
        key = self.wave_key()
        if key != self.key:
            self.key, self.wave = key, self.wave + 1
            self.on_wave()
        if self.supply is not None and not list.__len__(self):
            list.append(self, self.supply())
        r = list.pop(self, i)
        r.admitted = time.perf_counter()
        r.wave = self.wave
        return r


def check_layout(made, want):
    """The benchmark's weights must have the program's layout."""
    a = jax.tree.structure(made)
    b = jax.tree.structure(want)
    if a != b:
        raise ValueError(f"weight tree {a} differs from the program's {b}")
    for x, y in zip(jax.tree.leaves(made), jax.tree.leaves(want)):
        if x.shape != y.shape or x.dtype != y.dtype:
            raise ValueError(f"weight {x.shape} {x.dtype} differs from the "
                             f"program's {y.shape} {y.dtype}")


class Driver:
    # the numbers `check` compares, each with a limit in the configuration
    CHECKS = ("requests_malformed", "logit_gap")

    def __init__(self, cell, seed: int):
        self.cell, self.seed = cell, seed
        self.spec = cell.family.spec(cell.config)
        self.mcfg = cell.family.model_config(self.spec)
        sv = cell.config["serving"]
        self.batch, self.max_len = sv["batch"], sv["max_len"]
        self.ref = load_module("reference", cell.config["reference"],
                               cell.root)
        self.gen = load_module("traffic", cell.traffic["generator"],
                               cell.root)
        self.closed = cell.traffic["arrival"]["kind"] == "closed"
        self.engine = None
        self.requests: list = []

    # ------------------------------------------------------------ set-up
    def setup(self):
        from repro.models.transformer import init_params
        from repro.serving.engine import Request, ServeEngine
        want = jax.eval_shape(
            lambda: init_params(jax.random.key(0), self.mcfg))
        self.vocab_rows = want["embed"].shape[0]
        params = self.ref.make_params(self.spec, self.seed, self.vocab_rows)
        check_layout(params, want)
        self.Request = Request
        self.engine = ServeEngine(self.mcfg, params=params, batch=self.batch,
                                  max_len=self.max_len)
        del params
        # one short wave compiles the step, the cache reset and the pick
        for i in range(self.batch):
            self.engine.submit(Request(rid=-1 - i, prompt=[1, 2], max_new=2))
        self.engine.run()
        self.engine.completed.clear()
        jax.block_until_ready(self.engine.cache)
        self.stream = self.gen.Stream(self.cell.traffic, self.seed,
                                      self.spec.vocab)

    def _request(self, spec: dict):
        r = self.Request(rid=len(self.requests), prompt=spec["prompt"],
                         max_new=spec["max_new"], out=TimedTokens())
        r.due = r.submitted = r.admitted = r.wave = None
        self.requests.append(r)
        return r

    # ------------------------------------------------------------ window
    def counters(self) -> dict:
        return {}

    def window(self, seconds: float, stretch=None) -> dict:
        eng = self.engine
        steps = 0
        runs = [0]
        wake, fed = threading.Event(), threading.Event()
        poll = stretch.poll if stretch is not None else (lambda: None)

        def wave_key():
            return runs[0], eng.steps_used

        if self.closed:
            q = GatedQueue(supply=lambda: self._request(
                self.stream.next_request()), wave_key=wave_key,
                on_wave=poll)
            sched, limit = [], seconds
        else:
            q = GatedQueue(wave_key=wave_key, on_wave=poll)
            sched = self.stream.schedule(seconds)
            limit = seconds + self.cell.traffic["arrival"].get("drain_s",
                                                               DRAIN_S)
        eng.queue = q

        def close():
            q.closed = True
            wake.set()

        def feed():
            for due, spec in sched:
                delay = t0 + due - time.perf_counter()
                if delay > 0:
                    time.sleep(delay)
                r = self._request(spec)
                r.due = t0 + due
                r.submitted = time.perf_counter()
                list.append(q, r)
                wake.set()
            fed.set()
            wake.set()

        threads = [threading.Timer(limit, close)]
        if sched:
            threads.append(threading.Thread(target=feed, daemon=True))
        else:
            fed.set()
        with jax.profiler.TraceAnnotation(WINDOW_SPAN):
            t0 = time.perf_counter()
            if stretch is not None:
                stretch.open(t0)
            for th in threads:
                th.start()
            while True:
                done = fed.is_set()     # before looking at the queue
                if q:
                    runs[0] += 1
                    with jax.profiler.TraceAnnotation(RUN_SPAN):
                        eng.run(max_steps=10**9)
                    steps += eng.steps_used
                    continue
                if done or q.closed:
                    break
                # the engine is idle: a stretch in progress may end here,
                # with its last wave, and not at the next arrival
                idle_poll = stretch is not None and stretch.a is not None \
                    and stretch.b is None
                if idle_poll:
                    stretch.poll()
                with jax.profiler.TraceAnnotation(WAIT_SPAN):
                    wake.wait(IDLE_POLL_S if idle_poll else None)
                wake.clear()
            t1 = time.perf_counter()
            if stretch is not None:
                stretch.close()
        threads[0].cancel()
        for th in threads:
            th.join()
        self.t0, self.t1 = t0, t1
        reqs = [r for r in self.requests
                if (r.due if r.due is not None else r.admitted) is not None]
        rec = self._record(reqs, t1)
        rec["seconds"] = t1 - t0
        rec["counters"]["steps"] = steps
        return rec

    def stretch_record(self, stretch) -> dict:
        """The record of the waves the traced stretch holds: those
        admitted inside it, each with its steps; the generator's lateness
        of the requests due inside it."""
        a, b = stretch.a, stretch.b
        reqs = [r for r in self.requests
                if r.admitted is not None and a <= r.admitted < b]
        rec = self._record(reqs, b)
        rec["seconds"] = b - a
        rec["counters"]["steps"] = self.wave_steps(reqs)
        rec["samples"]["gen_lag_s"] = [
            r.submitted - r.due for r in self.requests
            if r.due is not None and r.submitted is not None
            and a <= r.due < b]
        return rec

    @staticmethod
    def wave_steps(reqs) -> int:
        """Engine steps of the waves these requests make up: a lockstep
        wave runs its longest prompt and then its longest output."""
        waves: dict = {}
        for r in reqs:
            waves.setdefault(r.wave, []).append(r)
        return sum(max(len(r.prompt) for r in w) + max(r.max_new for r in w)
                   for w in waves.values())

    def _record(self, reqs, t_end) -> dict:
        served = [r for r in reqs if r.done]
        ttft, missing, itl, lag = [], [], [], []
        useful = 0
        for r in reqs:
            start = r.due if r.due is not None else r.admitted
            if r.out.times:
                ttft.append(r.out.times[0] - start)
            else:
                missing.append(t_end - start)
            itl.extend(np.diff(r.out.times).tolist())
            if r.due is not None and r.submitted is not None:
                lag.append(r.submitted - r.due)
            if r.done:
                useful += len(r.prompt) + len(r.out) - 1
        return {
            "attempted": len(reqs),
            "failed": len(reqs) - len(served),
            "samples": {"ttft_s": ttft, "ttft_missing_s": missing,
                        "itl_s": itl, "gen_lag_s": lag},
            "counters": {
                "tokens": sum(len(r.out) for r in served),
                "batch": self.batch,
                "useful_token_steps": useful,
                "sequences": [(len(r.prompt) + len(r.out) - 1)
                              for r in served],
            },
        }

    # ------------------------------------------------------------- check
    def release(self):
        """Free the program's state before the reference runs."""
        self.engine = None
        import gc
        gc.collect()

    def _sample(self):
        """The longest finished request and others drawn from the seed."""
        done = [r for r in self.requests if r.done]
        if not done:
            return []
        rng = np.random.default_rng([self.seed, 7])
        longest = max(done, key=lambda r: (len(r.prompt) + len(r.out),
                                           -r.rid))
        pick, tokens = [longest], len(longest.out)
        for i in rng.permutation(len(done)):
            if len(pick) >= CHECK_MAX_REQUESTS or tokens >= CHECK_TOKENS:
                break
            if done[i] is not longest:
                pick.append(done[i])
                tokens += len(done[i].out)
        return pick

    def _sequences(self, pick):
        """Inputs (prompt and served tokens but the last), the served
        token due at each position, and where one is due."""
        T = max(len(r.prompt) + len(r.out) - 1 for r in pick)
        toks = np.zeros((len(pick), T), np.int32)
        want = np.zeros((len(pick), T), np.int32)
        due = np.zeros((len(pick), T), bool)
        for b, r in enumerate(pick):
            seq = list(r.prompt) + list(r.out)[:-1]
            toks[b, :len(seq)] = seq
            L = len(r.prompt)
            want[b, L - 1:L - 1 + len(r.out)] = list(r.out)
            due[b, L - 1:L - 1 + len(r.out)] = True
        return jnp.asarray(toks), jnp.asarray(want), jnp.asarray(due)

    def _reference_logits(self, toks, cast=None):
        params = self.ref.make_params(self.spec, self.seed, self.vocab_rows)
        return self.ref.logits(params, self.spec, toks, cast=cast)

    @staticmethod
    def _widest_gap(logits, chosen, due):
        best = logits.max(-1)
        got = jnp.take_along_axis(logits, chosen[..., None], -1)[..., 0]
        return float(jnp.where(due, best - got, 0.0).max())

    def check(self) -> dict:
        vocab = self.spec.vocab
        served = [r for r in self.requests if r.done]
        bad = sum(1 for r in served
                  if len(r.out) != r.max_new
                  or any(not 0 <= t < vocab for t in r.out))
        pick = self._sample()
        out = {"requests_malformed": bad}
        if not pick:
            out["logit_gap"] = float("inf")
            return out
        toks, want, due = self._sequences(pick)
        lg = self._reference_logits(toks)
        out["logit_gap"] = self._widest_gap(lg, want, due)
        out["checked_tokens"] = int(due.sum())
        return out

    def control(self) -> dict:
        """The reference in 8-bit floating point weights, at the same
        positions: the widest gap of the token it puts first."""
        pick = self._sample()
        toks, _, due = self._sequences(pick)
        lg = self._reference_logits(toks)
        low = self._reference_logits(toks, cast=lowp.fp8)
        return {"logit_gap": self._widest_gap(lg, low.argmax(-1), due)}
