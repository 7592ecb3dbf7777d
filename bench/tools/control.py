"""Read the control of a cell on several seeds, on the chip, at the cell's
own size and load: the reference in the program's place, one precision
below the configuration's, read by the same comparison that decides
`correct`.  Prints one JSON line per seed with the program's readings of
the same window beside the control's.

    python3 bench/tools/control.py --workload <cell> --seconds <s> <seed> ...

One process holds the chip: run it alone.
"""
import argparse
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench.lib import device  # noqa: E402
from bench.lib.cell import load_cell  # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("seeds", type=int, nargs="+")
    args = ap.parse_args()
    cell = load_cell(args.workload, ROOT)
    device.require(cell.chips)
    from bench.lib.harness import control_cell
    for seed in args.seeds:
        r = control_cell(cell, seed, args.seconds)
        print(json.dumps({"workload": cell.name, "seed": seed, **r}),
              flush=True)


if __name__ == "__main__":
    main()
