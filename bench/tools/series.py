"""Run cells of the benchmark one after another, each in a process of its
own (one process holds the chip at a time), and keep every result.

    python3 bench/tools/series.py --out bench/out/series.jsonl \
        stablelm3b.batch:101:45:0 stablelm3b.batch:102:45:1 ...

Each item is cell:seed:seconds:trace.  Appends one JSON line per run to
--out: the item, the exit code, the wall time, the result line and the
end of standard error.  This process never imports JAX.
"""
import argparse
import json
import pathlib
import subprocess
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[2]


def run_one(item: str, timeout: float) -> dict:
    cell, seed, seconds, trace = item.split(":")
    cmd = [sys.executable, str(ROOT / "bench" / "run.py"), "--workload",
           cell, "--seed", seed, "--seconds", seconds, "--trace", trace]
    t = time.perf_counter()
    try:
        p = subprocess.run(cmd, cwd=ROOT, capture_output=True, text=True,
                           timeout=timeout)
        rc, out, err = p.returncode, p.stdout, p.stderr
    except subprocess.TimeoutExpired as e:
        rc, out, err = 124, e.stdout or "", e.stderr or ""
        out = out if isinstance(out, str) else out.decode()
        err = err if isinstance(err, str) else err.decode()
    lines = [ln for ln in out.splitlines() if ln.strip()]
    result = None
    if lines:
        try:
            result = json.loads(lines[-1])
        except json.JSONDecodeError:
            result = None
    return {"item": item, "rc": rc, "wall_s": time.perf_counter() - t,
            "result": result, "stderr_tail": err[-3000:]}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", required=True)
    ap.add_argument("--timeout", type=float, default=1200)
    ap.add_argument("items", nargs="+")
    args = ap.parse_args()
    out = pathlib.Path(args.out)
    out.parent.mkdir(parents=True, exist_ok=True)
    for item in args.items:
        rec = run_one(item, args.timeout)
        with out.open("a") as f:
            f.write(json.dumps(rec) + "\n")
        r = rec["result"] or {}
        m = {k: round(v["value"], 4) for k, v in r.get("metrics", {}).items()}
        c = {k: v["value"] for k, v in r.get("checks", {}).items()}
        print(f"{item} rc={rec['rc']} wall={rec['wall_s']:.1f}s "
              f"correct={r.get('correct')} {m} {c}", flush=True)
        if rec["rc"] != 0 or not r:
            print(rec["stderr_tail"][-1500:], flush=True)


if __name__ == "__main__":
    main()
