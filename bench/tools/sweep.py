"""Find the highest rate an open-loop serving cell sustains, in one
process on the chip, and check the rate offered at a share of it.

    python3 bench/tools/sweep.py --workload mamba2.chat --seconds 45 \
        --seed 3 0.6 0.8 1.0 1.2

The lockstep engine admits a wave of up to `batch` requests and serves it
to its end; past its capacity every wave is full.  So the highest rate it
sustains is what it completes in a closed loop of the cell's own request
sizes, which this measures first.  Then, for each share of that rate, an
open-loop window: requests due and served, and the median time to first
token of the requests due in the first and in the second half of the
window.  A backlog that grows shows as a second half much slower than
the first.  The cell's traffic file takes 0.8 of the capacity.
"""
import argparse
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench.lib import device, stats  # noqa: E402
from bench.lib.cell import load_cell, load_module  # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("shares", type=float, nargs="+")
    args = ap.parse_args()
    cell = load_cell(args.workload, ROOT)
    device.require(cell.chips)
    from bench.lib.harness import use_compile_cache
    use_compile_cache()
    drv = load_module("drivers", cell.traffic["driver"], ROOT).Driver(
        cell, args.seed)
    drv.setup()
    gen = load_module("traffic", cell.traffic["generator"], ROOT)

    def window(arrival):
        t = dict(cell.traffic, arrival=arrival)
        drv.closed = arrival["kind"] == "closed"
        drv.stream = gen.Stream(t, args.seed, drv.spec.vocab)
        drv.requests = []
        return drv.window(args.seconds)

    rec = window({"kind": "closed"})
    done = rec["attempted"] - rec["failed"]
    capacity = done / rec["seconds"]
    print(json.dumps({"capacity_req_per_s": capacity, "completed": done,
                      "window_s": rec["seconds"]}), flush=True)
    for share in args.shares:
        rate = share * capacity
        rec = window(dict(cell.traffic["arrival"], kind="gamma",
                          rate_per_s=rate))
        half = drv.t0 + args.seconds / 2
        ttft = [(r.due, r.out.times[0] - r.due) for r in drv.requests
                if r.out.times]
        first = [t for d, t in ttft if d < half]
        second = [t for d, t in ttft if d >= half]
        print(json.dumps({
            "share": share, "rate": rate, "due": rec["attempted"],
            "unserved": rec["failed"],
            "ttft_p50_first_s": stats.percentile(first, 50) if first else None,
            "ttft_p50_second_s": stats.percentile(second, 50)
            if second else None,
            "ttft_p95_s": stats.percentile_with_missing(
                rec["samples"]["ttft_s"], rec["samples"]["ttft_missing_s"],
                95),
            "window_s": rec["seconds"]}), flush=True)


if __name__ == "__main__":
    main()
