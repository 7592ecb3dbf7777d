"""Benchmark harness entry point.  One section per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--quick|--full] [--sanitize] \
        [names...]

Prints `name,us_per_call,derived` CSV lines.  `--quick` shrinks the
simulated DB and op counts; default profile matches the paper's ratios
at laptop scale.  `--sanitize` wraps every engine in the runtime
sanitizer (core/sanitize.py) — much slower, but every op is checked
against the invariant suite.  Optional positional names select a
subset, e.g. `python -m benchmarks.run ycsb ablations`.
"""
from __future__ import annotations

import sys
import time
import traceback

from repro.launch.compile_cache import setup_compile_cache

from . import (ablations, cost_breakdown, dynamic_workload, kernel_bench,
               ralt_micro, shifting_hotspot, tail_latency, tiered_serving,
               twitter_traces, wa_tuning, ycsb_scan, ycsb_shard,
               ycsb_throughput)

SECTIONS = [
    ("ycsb", ycsb_throughput.main),          # Fig. 6 & 7
    ("scan", ycsb_scan.main),                # YCSB-E (scan subsystem)
    ("shard", ycsb_shard.main),              # sharded scaling + HotBudget
    ("repart", shifting_hotspot.main),       # dynamic repartitioning
    ("tail", tail_latency.main),             # Fig. 8
    ("twitter", twitter_traces.main),        # Fig. 9-11
    ("breakdown", cost_breakdown.main),      # Fig. 12-14
    ("ablations", ablations.main),           # Tables 3 & 4
    ("dynamic", dynamic_workload.main),      # Fig. 15
    ("ralt", ralt_micro.main),               # §3.2
    ("wa", wa_tuning.main),                  # §3.6
    ("kernels", kernel_bench.main),          # Pallas kernels
    ("serving", tiered_serving.main),        # tiered KV/embedding/experts
]


def main() -> None:
    args = [a for a in sys.argv[1:] if not a.startswith("-")]
    quick = "--quick" in sys.argv
    selected = [(n, f) for n, f in SECTIONS if not args or n in args]
    setup_compile_cache()
    failures = []
    for name, fn in selected:
        t0 = time.time()
        print(f"# === {name} ===", flush=True)
        try:
            fn(quick=quick)
        except Exception:
            failures.append(name)
            traceback.print_exc()
        print(f"# === {name} done in {time.time() - t0:.1f}s ===",
              flush=True)
    if failures:
        print(f"# FAILED sections: {failures}", flush=True)
        sys.exit(1)


if __name__ == "__main__":
    main()
