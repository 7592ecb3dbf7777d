"""Chip smoke test: serve stablelm-3b at its published widths on one TPU
and drive the RALT-tracked tiered KV cache at its page geometry.

    python3 chip_smoke.py

Phases, in order; any failure exits non-zero:

  device     JAX must find a TPU; otherwise exit non-zero with no result.
  serve      `repro.launch.serve.serve` (what `python -m repro.launch.serve
             --arch stablelm-3b` runs) with random weights from SEED: two
             waves of the engine's batch.  Every request gets exactly
             `max_new` in-vocabulary tokens; the engine's logits after
             prefill are finite and agree with `models.transformer.forward`.
  tiered KV  `TieredKVCache` with more pages than HBM slots under the
             access pattern of `examples/serve_tiered_kv.py`: retention,
             promotion by compaction and promotion by flush each fire,
             reads return the pages written, the tracker's record step
             holds the Pallas kernel, and `ralt_update` on the chip matches
             its reference.

Times printed are host wall clock; none is a device metric.  The last
line of standard output is one JSON object naming the device.
"""
from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.kernels import ops, ref  # noqa: E402
from repro.launch.compile_cache import setup_compile_cache  # noqa: E402
from repro.launch.serve import serve  # noqa: E402
from repro.models.transformer import forward  # noqa: E402
from repro.obs.serving import ServingObservability  # noqa: E402
from repro.tiering import KVTierConfig, TieredKVCache  # noqa: E402

ARCH = "stablelm-3b"
SEED = 0
# Two waves of the engine's batch.  The decode step at B=8 with a
# 1024-token cache compiles to 10.4 GiB for a v5e; these prompts need a
# 168-token cache, well inside that.
BATCH, PROMPT_LEN, MAX_NEW, N_REQUESTS = 8, 128, 32, 16
# Engine (teacher-forced decode steps) against `forward` (one blocked
# attention pass) in bf16: the two paths round differently through 32
# layers; max |difference| over max |reference logit|.
LOGIT_TOL = 0.05
# Tiered KV: 16 tokens/page over every layer and KV head, 512 pages over
# 128 HBM slots; the traffic is long enough for all three pathways.
N_PAGES, FAST_SLOTS, PAGE_TOKENS, KV_STEPS = 512, 128, 16, 1200
HOT_BASE = 64                   # start of the hot middle segment
RALT_N = 100_000                # not a multiple of the kernel's block

COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")


def log(msg: str) -> None:
    print(msg, flush=True)


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SystemExit(f"chip_smoke FAILED: {what}")


def device_phase():
    devs = jax.devices()
    d = devs[0]
    log(f"[device] platform={d.platform} kind={d.device_kind} "
        f"count={len(devs)}")
    if d.platform != "tpu":
        raise SystemExit(f"chip_smoke needs a TPU; JAX found {d.platform!r}")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


def serve_phase(cfg, *, batch=BATCH, prompt_len=PROMPT_LEN,
                max_new=MAX_NEW, n_requests=N_REQUESTS):
    log(f"[serve] {cfg.name}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
        f"{cfg.n_heads} heads x {cfg.head_dim} (kv {cfg.n_kv_heads}), "
        f"d_ff {cfg.d_ff}, vocab {cfg.vocab}, "
        f"{cfg.param_count() / 1e9:.2f} B params, {cfg.dtype}")
    eng, done, wall = serve(cfg, n_requests=n_requests,
                            prompt_len=prompt_len, max_new=max_new,
                            batch=batch, seed=SEED)
    tokens = sum(len(r.out) for r in done)
    log(f"[serve] {len(done)}/{n_requests} requests, {tokens} tokens, "
        f"{eng.steps_used} engine steps, host wall {wall:.3f} s "
        f"(compile included)")
    check(len(done) == n_requests and not eng.starved,
          f"{len(done)} of {n_requests} requests completed")
    check(all(len(r.out) == max_new for r in done),
          "a request did not get exactly max_new tokens")
    check(all(0 <= t < cfg.vocab for r in done for t in r.out),
          "a token outside the vocabulary")

    # Replay the first wave's prefill and read the engine's logits.
    wave = sorted(done, key=lambda r: r.rid)[:batch]
    prompts = np.asarray([r.prompt for r in wave], np.int32)
    t0 = time.perf_counter()
    eng.reset()
    for t in range(prompt_len):
        logits = eng.step(prompts[:, t], t)
    got = np.asarray(logits[:, :cfg.vocab], np.float32)
    log(f"[serve] prefill replay: {prompt_len} steps, host wall "
        f"{time.perf_counter() - t0:.3f} s")
    check(bool(np.isfinite(got).all()), "non-finite engine logits")
    check([int(i) for i in got.argmax(-1)] == [r.out[0] for r in wave],
          "served first tokens differ from the replayed prefill")
    want = jax.jit(forward, static_argnums=1)(eng.params, cfg,
                                              jnp.asarray(prompts))
    want = np.asarray(want[:, -1, :cfg.vocab], np.float32)
    check(bool(np.isfinite(want).all()), "non-finite forward logits")
    err = float(np.abs(got - want).max() / np.abs(want).max())
    top1 = float((got.argmax(-1) == want.argmax(-1)).mean())
    log(f"[serve] engine vs forward at position {prompt_len - 1}: max |diff| "
        f"/ max |logit| = {err:.3e} (tol {LOGIT_TOL}), top-1 agreement "
        f"{top1:.3f}")
    check(err <= LOGIT_TOL, f"engine logits differ from forward by {err:.3e}")


def page_access_pattern(rng, step, n_pages):
    """`examples/serve_tiered_kv.py`: attention sinks, the local window,
    a hot middle segment, and an occasional scan."""
    pages = {0, 1}
    tail = n_pages - 1 - (step % 8)
    pages |= {max(tail - i, 0) for i in range(3)}
    pages |= {HOT_BASE + int(i) for i in rng.integers(0, 12, 4)}
    if rng.random() < 0.2:
        pages.add(int(rng.integers(0, n_pages)))
    return sorted(pages)


def drive_tiered_kv(kvcfg, steps=KV_STEPS):
    """Fill every page with its id (mod 256, exact in bf16), run the
    traffic, and return (cache, pages promoted by compaction, by flush)."""
    kv = TieredKVCache(kvcfg)
    obs = ServingObservability(attribution=False)
    obs.attach(kv, "kv")
    shape = (kvcfg.n_layers, kvcfg.page_tokens, kvcfg.kv_heads,
             kvcfg.head_dim)
    for p in range(kvcfg.n_pages):
        blob = np.full(shape, p % 256, jnp.dtype(kvcfg.dtype))
        kv.write_page(p, blob, blob)
    rng = np.random.default_rng(SEED)
    for step in range(steps):
        pages = page_access_pattern(rng, step, kvcfg.n_pages)
        got = kv.read_pages(pages)
    for p, page in zip(pages, got):
        check(bool(jnp.all(page == p % 256)), f"page {p} read back wrong")
    promoted = {name: sum(ev["args"]["pages"] for ev in obs.tracer.events
                          if ev["name"] == name)
                for name in ("page/promo_compaction", "page/promo_flush")}
    return kv, promoted["page/promo_compaction"], promoted["page/promo_flush"]


def tiered_kv_phase(cfg):
    kvcfg = KVTierConfig(n_pages=N_PAGES, fast_slots=FAST_SLOTS,
                         page_tokens=PAGE_TOKENS, kv_heads=cfg.n_kv_heads,
                         head_dim=cfg.head_dim, n_layers=cfg.n_layers,
                         dtype=cfg.dtype)
    log(f"[tiered-kv] {kvcfg.n_pages} pages over {kvcfg.fast_slots} HBM "
        f"slots, {kvcfg.page_tokens} tokens x {kvcfg.kv_heads} KV heads x "
        f"{kvcfg.head_dim} x {kvcfg.n_layers} layers, {kvcfg.dtype}: "
        f"{kvcfg.page_bytes / 2**20:.2f} MiB per page")
    t0 = time.perf_counter()
    kv, by_compaction, by_flush = drive_tiered_kv(kvcfg)
    c = kv.clock
    log(f"[tiered-kv] {KV_STEPS} steps, host wall "
        f"{time.perf_counter() - t0:.3f} s; SimClock counters: "
        f"retained={c.retained} promoted={c.promoted} (compaction "
        f"{by_compaction}, flush {by_flush}) demoted={c.demoted} "
        f"aborted={c.aborted} sweeps={c.sweeps} flushes={c.flushes} "
        f"fast_hit_rate={kv.fast_hit_rate():.3f}")
    check(c.retained > 0, "retention never fired")
    check(by_compaction > 0, "promotion by compaction never fired")
    check(by_flush > 0 and c.flushes > 0, "promotion by flush never fired")

    tr = kv.tracker
    mask = jnp.zeros(tr.cfg.n_units, bool).at[:4].set(True)
    lowered = tr._record.lower(tr.state, mask).as_text()
    check("tpu_custom_call" in lowered,
          "the tracker's record step holds no Pallas kernel")
    log("[tiered-kv] tracker record step lowers to tpu_custom_call")

    rng = np.random.default_rng(SEED)
    ticks = jnp.asarray(rng.integers(0, 50, RALT_N), jnp.int32)
    scores = jnp.asarray(rng.random(RALT_N) * 5, jnp.float32)
    hits = jnp.asarray(rng.integers(0, 2, RALT_N), jnp.int8)
    now, thresh, alpha = 57, 1.0, 0.999
    nt, ns, hot = ops.ralt_update(ticks, scores, hits, now, thresh,
                                  alpha=alpha)
    wt, ws = ref.ralt_update_ref(ticks, scores, hits, now, alpha)
    ns, ws = np.asarray(ns), np.asarray(ws)
    check(np.array_equal(np.asarray(nt), np.asarray(wt)),
          "ralt_update ticks differ from the reference")
    err = float(np.abs(ns - ws).max())
    log(f"[tiered-kv] ralt_update N={RALT_N}: max |score - ref| = {err:.3e}")
    check(np.allclose(ns, ws, rtol=1e-5, atol=1e-5),
          "ralt_update scores differ from the reference")
    check(np.array_equal(np.asarray(hot) != 0, ns >= thresh),
          "ralt_update hot bitmap disagrees with its scores")


def main() -> None:
    device = device_phase()
    cache_dir = setup_compile_cache()
    compile_s = [0.0]

    def on_event(event, duration, **_):
        if event in COMPILE_EVENTS:
            compile_s[0] += duration

    jax.monitoring.register_event_duration_secs_listener(on_event)
    cfg = get_config(ARCH)
    serve_phase(cfg)
    log(f"[serve] compile (trace + lower + XLA) {compile_s[0]:.3f} s, "
        f"cache {cache_dir}")
    tiered_kv_phase(cfg)
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
