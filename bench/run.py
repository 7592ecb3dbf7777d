"""Run one cell of the on-chip benchmark.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Prints, as the last line of standard output, one JSON object: `correct`,
`attempted`, `failed`, `metrics` (the cell's end-to-end metrics, or with
`--trace 1` its per-layer metrics), `device`, with `--trace 1` a
`breakdown`, and last `checks`: each number compared with the reference
beside its limit (also the last lines of standard error).

Exits non-zero with no result line where JAX finds no TPU, or fewer chips
than the cell asks for.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
# the TPU runtime would otherwise keep its logs at a fixed path in /tmp
os.environ.setdefault("TPU_LOG_DIR", "disabled")

from bench.lib import device  # noqa: E402
from bench.lib.cell import load_cell  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = load_cell(args.workload, ROOT)
    try:
        device.require(cell.chips)
    except device.NoDevice as e:
        print(f"[run] {e}", file=sys.stderr)
        return 3
    import jax
    from bench.lib.harness import run_cell
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      T_START, devs=jax.devices()[:cell.chips])
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
