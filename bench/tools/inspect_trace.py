"""Print what a profiler trace holds (planes, lines, the most frequent
event names) and optionally write a clipped copy as a test fixture.

    python3 bench/tools/inspect_trace.py <trace.xplane.pb> [fixture.json ms]
"""
import collections
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(path, fixture=None, ms=None):
    from jax.profiler import ProfileData
    from bench.lib import trace
    pd = ProfileData.from_file(path)
    for plane in pd.planes:
        lines = list(plane.lines)
        print(f"PLANE {plane.name!r}: {len(lines)} lines")
        for line in lines:
            names = collections.Counter(e.name for e in line.events)
            print(f"  LINE {line.name!r}: {sum(names.values())} events; "
                  f"{names.most_common(8)}")
    data = trace.load_xplane(path)
    red = trace.reduce(data)
    print(f"window {red.window_s:.3f} s busy {red.busy_s:.3f} s "
          f"idle {red.idle_share:.3f}")
    print(red.breakdown())
    if fixture:
        lo = red.lo
        hi = lo + int(float(ms) * 1e6)
        spans = [(s, e, n) for s, e, n in data.spans if s < hi]
        spans = [(s, min(e, hi), n) for s, e, n in spans]
        clipped = trace.TraceData(
            ops={k: [r for r in v if lo <= r[0] and r[1] <= hi]
                 for k, v in data.ops.items()},
            modules={k: [r for r in v if lo <= r[0] and r[1] <= hi]
                     for k, v in data.modules.items()},
            spans=spans)
        trace.save_fixture(clipped, fixture)
        print(f"fixture {fixture}")


if __name__ == "__main__":
    main(*sys.argv[1:])
