"""Spreads of a cell's two sets of runs, as the bounds are set from them.

    python3 bench/tools/spread.py bench/out/series.jsonl [more.jsonl]

Reads the result lines that `series.py` wrote.  Untraced runs of one cell
and one window length are split, in the order they ran, into a first and
a second set of equal length (the same seeds in each).  For each end-to-end metric: each set's
median and spread (interquartile distance over the median, Python's
quartiles), the wider spread, five times it, and the second median
against the first.  Also every `checks` reading per seed.
"""
import collections
import json
import pathlib
import statistics
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[2]))

from bench.lib.stats import spread  # noqa: E402


def main(paths):
    runs = collections.defaultdict(list)
    checks = collections.defaultdict(list)
    for path in paths:
        for line in open(path):
            rec = json.loads(line)
            cell, seed, seconds, trace = rec["item"].split(":")
            r = rec["result"]
            if not r:
                print(f"{rec['item']}: no result (rc {rec['rc']})")
                continue
            for k, c in r["checks"].items():
                checks[(cell, k)].append((int(seed), c["value"]))
            if trace == "0":
                runs[f"{cell} at {seconds} s"].append(r)
    for cell, rs in runs.items():
        half = len(rs) // 2
        if half < 2:
            continue
        sets = [rs[:half], rs[half:2 * half]]
        names = sorted(rs[0]["metrics"])
        print(f"{cell}: {len(rs)} runs, sets of {half}")
        for m in names:
            vals = [[r["metrics"][m]["value"] for r in s] for s in sets]
            sp = [spread(v) for v in vals]
            med = [statistics.median(v) for v in vals]
            print(f"  {m}: medians {med[0]:.6g} / {med[1]:.6g} "
                  f"(second vs first {med[1] / med[0] - 1:+.4%}); spreads "
                  f"{sp[0]:.4%} / {sp[1]:.4%}; 5 x wider {5 * max(sp):.4%}")
            print(f"    set 1: {vals[0]}\n    set 2: {vals[1]}")
        mem = [r["device"]["memory_peak_bytes"] for r in rs]
        print(f"  memory_peak_bytes: {min(mem)} .. {max(mem)}")
    for (cell, k), vals in sorted(checks.items()):
        v = [x for _, x in vals]
        print(f"{cell} {k}: n={len(v)} seeds={len({s for s, _ in vals})} "
              f"max={max(v):.6g} min={min(v):.6g}")


if __name__ == "__main__":
    main(sys.argv[1:])
