"""Kernel micro-bench: Pallas vs jnp reference wall time, with the
*analytic* TPU projection from tile shapes.

The kernels run as `repro.kernels.ops.interpret_mode` decides: compiled
on a TPU, interpreted on the CPU backend.  Interpreted wall times are
NOT TPU performance; there the value of this section is (a) correctness
at benchmark shapes and (b) the VMEM/MXU roofline sanity of the chosen
block shapes, printed per kernel.

Observability (PR 7): each kernel's reference and Pallas timings run
inside flight-recorder spans on a *wall-clock* tracer (the simulated
engine uses sim-time clocks; here `time.perf_counter` is the honest
axis), and the kernels themselves carry `jax.profiler` trace
annotations (see `repro.kernels.ralt_score`), so a TensorBoard/XLA
profile of a real TPU run shows the same span names as this bench's
Perfetto export.  `--trace[=path]` writes the trace;
`--smoke` gates max-error per kernel and writes ``BENCH_kernels.json``.
"""
from __future__ import annotations

import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops, ref
from repro.obs import Tracer

from .common import flag_value, write_bench_json

SMOKE_MAX_ERR = 5e-3


def timeit(fn, *args, iters=3):
    fn(*args)[0].block_until_ready() if isinstance(fn(*args), tuple) \
        else fn(*args).block_until_ready()
    t0 = time.time()
    for _ in range(iters):
        out = fn(*args)
        (out[0] if isinstance(out, tuple) else out).block_until_ready()
    return (time.time() - t0) / iters


def main(quick: bool = False) -> dict:
    tracer = Tracer(clock=time.perf_counter)
    trace_path = flag_value("--trace", "trace_kernels.json")
    rows: dict = {}

    def timed(kernel: str, which: str, fn, *args):
        with tracer.span("kernels", f"{kernel}/{which}"):
            return timeit(fn, *args)

    S = 256 if quick else 512
    B, H, KVH, D = 1, 4, 2, 64
    q = jax.random.normal(jax.random.key(0), (B, S, H, D), jnp.float32)
    k = jax.random.normal(jax.random.key(1), (B, S, KVH, D), jnp.float32)
    v = jax.random.normal(jax.random.key(2), (B, S, KVH, D), jnp.float32)

    t_ref = timed("flash_attention", "ref",
                  lambda a, b, c: ref.flash_attention_ref(a, b, c), q, k, v)
    t_pal = timed("flash_attention", "pallas",
                  lambda a, b, c: ops.flash_attention(
                      a, b, c, block_q=128, block_k=128),
                  q, k, v)
    err = float(jnp.abs(
        ops.flash_attention(q, k, v, block_q=128, block_k=128)
        - ref.flash_attention_ref(q, k, v)).max())
    vmem_kib = (128 * D * 4 * 2 + 128 * D * 4 + 128 * 128 * 4) / 1024
    rows["flash_attention"] = {"pallas_us": t_pal * 1e6,
                               "ref_us": t_ref * 1e6, "max_err": err,
                               "tile_vmem_kib": vmem_kib}
    print(f"flash_attention,{t_pal * 1e6:.0f},pallas_us "
          f"ref_us={t_ref * 1e6:.0f} max_err={err:.1e} "
          f"tile_vmem={vmem_kib:.0f}KiB", flush=True)

    qd = jax.random.normal(jax.random.key(3), (B, H, D), jnp.float32)
    t_ref = timed("decode_attention", "ref",
                  lambda a, b, c: ref.decode_attention_ref(a, b, c, S),
                  qd, k, v)
    t_pal = timed("decode_attention", "pallas",
                  lambda a, b, c: ops.decode_attention(
                      a, b, c, jnp.int32(S), block_s=128),
                  qd, k, v)
    err = float(jnp.abs(
        ops.decode_attention(qd, k, v, jnp.int32(S), block_s=128)
        - ref.decode_attention_ref(qd, k, v, S)).max())
    rows["decode_attention"] = {"pallas_us": t_pal * 1e6,
                                "ref_us": t_ref * 1e6, "max_err": err}
    print(f"decode_attention,{t_pal * 1e6:.0f},pallas_us "
          f"ref_us={t_ref * 1e6:.0f} max_err={err:.1e} "
          f"bw_bound=True", flush=True)

    N = 4096 if quick else 65536
    rng = np.random.default_rng(0)
    ticks = jnp.asarray(rng.integers(0, 50, N), jnp.int32)
    scores = jnp.asarray(rng.random(N), jnp.float32)
    hits = jnp.asarray(rng.integers(0, 2, N), jnp.int8)
    t_pal = timed("ralt_update", "pallas",
                  lambda a, b, c: ops.ralt_update(
                      a, b, c, 60, 0.5)[1],
                  ticks, scores, hits)
    nt, ns, _ = ops.ralt_update(ticks, scores, hits, 60, 0.5)
    wt, ws = ref.ralt_update_ref(ticks, scores, hits, 60, 0.999)
    err = float(jnp.abs(ns - ws).max())
    rows["ralt_update"] = {"pallas_us": t_pal * 1e6, "n": N,
                           "max_err": err}
    print(f"ralt_update,{t_pal * 1e6:.0f},pallas_us n={N} "
          f"max_err={err:.1e} fused_passes=1", flush=True)

    Bz, nC, Q, nh, hp, ns_ = 1, 4, 64, 2, 64, 64
    x = jax.random.normal(jax.random.key(4), (Bz, nC, Q, nh, hp)) * 0.3
    Bm = jax.random.normal(jax.random.key(5), (Bz, nC, Q, ns_)) * 0.3
    Cm = jax.random.normal(jax.random.key(6), (Bz, nC, Q, ns_)) * 0.3
    dt = jax.nn.softplus(jax.random.normal(jax.random.key(7),
                                           (Bz, nC, Q, nh)))
    A = -jnp.exp(jax.random.normal(jax.random.key(8), (nh,)) * 0.1)
    t_pal = timed("ssd_scan", "pallas",
                  lambda *a: ops.ssd_scan(*a)[0],
                  x, Bm, Cm, dt, A)
    y, h = ops.ssd_scan(x, Bm, Cm, dt, A)
    wy, wh = ref.ssd_chunk_ref(x, Bm, Cm, dt, A,
                               jnp.zeros((Bz, nh, ns_, hp)))
    err = float(jnp.abs(y - wy).max())
    rows["ssd_scan"] = {"pallas_us": t_pal * 1e6, "max_err": err,
                        "state_vmem_kib": (ns_ * hp * 4) / 1024}
    print(f"ssd_scan,{t_pal * 1e6:.0f},pallas_us max_err={err:.1e} "
          f"state_vmem={(ns_ * hp * 4) / 1024:.0f}KiB", flush=True)

    if trace_path:
        tracer.export(trace_path)
        print(f"# wrote {trace_path}", flush=True)
    return rows


def smoke() -> None:
    """CI tripwire: every kernel within tolerance of its reference at
    smoke shapes, plus the machine-readable artifact."""
    rows = main(quick=True)
    write_bench_json("kernels", rows)
    failures = [f"{name} max_err {r['max_err']:.2e} > {SMOKE_MAX_ERR}"
                for name, r in rows.items()
                if r["max_err"] > SMOKE_MAX_ERR]
    if failures:
        for f in failures:
            print(f"SMOKE FAIL: {f}", flush=True)
        raise SystemExit(1)
    print(f"SMOKE OK: {len(rows)} kernels within {SMOKE_MAX_ERR} of "
          f"reference", flush=True)


if __name__ == "__main__":
    if "--smoke" in sys.argv:
        smoke()
    else:
        main(quick="--quick" in sys.argv)
