"""The program's own spans and counters, read from the in-process log of
its observability plane (`repro.obs.serving.DEFAULT_SERVING_OBS`).

That plane records while a `jax.profiler` session records, stamps its
events with the profiler's host clock, and starts an empty log when a new
session begins: after a traced stretch its log holds the stretch's spans.
A run that was not traced, or a program without that plane, has nothing to
read, and every reader built on this returns None.

Times in the log are microseconds since the epoch.  Each span knows its
parent (the span open on its track when it began); a span's self time is
its duration minus the part of it that its child spans cover.
"""
from __future__ import annotations

import dataclasses

from bench.lib import stats

MS = 1e-3               # milliseconds per microsecond
MIB = float(2**20)


@dataclasses.dataclass
class Span:
    name: str
    start: float            # microseconds since the epoch
    end: float
    id: int
    parent: int | None
    args: dict              # the begin event's
    end_args: dict          # the end event's

    @property
    def ms(self) -> float:
        return (self.end - self.start) * MS


class Log:
    """Closed spans, instants and the parent-child relation of one log."""

    def __init__(self, events):
        begun, self.spans, self.instants = {}, [], []
        for ev in events:
            if ev["ph"] == "B":
                begun[ev["id"]] = ev
            elif ev["ph"] == "E":
                b = begun.pop(ev.get("id"), None)
                if b is not None:
                    self.spans.append(Span(
                        b["name"], b["ts"], ev["ts"], b["id"], b["parent"],
                        b.get("args", {}), ev.get("args", {})))
            elif ev["ph"] == "i":
                self.instants.append(ev)
        self.children: dict = {}
        for s in self.spans:
            if s.parent is not None:
                self.children.setdefault(s.parent, []).append(s)

    def named(self, name: str) -> list:
        return [s for s in self.spans if s.name == name]

    def self_ms(self, span: Span) -> float:
        """Children on one track never overlap: their sum is the part of
        the span they cover."""
        return span.ms - sum(c.ms for c in self.children.get(span.id, ()))


def log(run) -> Log | None:
    """The program's log of this traced run's stretch, or None."""
    if run.trace is None:
        return None
    try:
        from repro.obs import serving
    except ImportError:
        return None
    plane = getattr(serving, "DEFAULT_SERVING_OBS", None)
    if plane is None or not plane.tracer.events:
        return None
    return Log(plane.tracer.events)


def _named(run, name: str):
    """(the log, its closed spans called `name`), or (None, [])."""
    lg = log(run)
    return lg, (lg.named(name) if lg else [])


def engine_host_ms_per_step(run):
    """Mean over `engine/step` spans of the step's duration minus its
    `engine/sync` child: the engine's host work per step."""
    lg, steps = _named(run, "engine/step")
    if not steps:
        return None
    return sum(s.ms - sum(c.ms for c in lg.children.get(s.id, ())
                          if c.name == "engine/sync")
               for s in steps) / len(steps)


def first_token_p95_ms(run):
    """95th percentile, over the requests admitted in the stretch, of
    `engine/first_token` minus `engine/admit` of the same request."""
    lg = log(run)
    if lg is None:
        return None
    at = {"engine/admit": {}, "engine/first_token": {}}
    for ev in lg.instants:
        if ev["name"] in at:
            at[ev["name"]][ev["args"]["rid"]] = ev["ts"]
    first = at["engine/first_token"]
    gaps = [first[rid] - t for rid, t in at["engine/admit"].items()
            if rid in first]
    return stats.percentile(gaps, 95) * MS if gaps else None


def h2d_mib_per_read(run):
    """Host-to-device bytes that the `kv/read_pages` spans moved (their
    end args), over their count."""
    _, reads = _named(run, "kv/read_pages")
    if not reads:
        return None
    return sum(s.end_args["h2d_bytes"] for s in reads) / len(reads) / MIB


def self_ms_per_read(run, match):
    """Self time of the spans whose names `match` accepts, summed over
    the stretch, over its `kv/read_pages` spans."""
    lg, reads = _named(run, "kv/read_pages")
    if not reads:
        return None
    return sum(lg.self_ms(s) for s in lg.spans if match(s.name)) / len(reads)


def mean_ms(run, name: str):
    _, spans = _named(run, name)
    return sum(s.ms for s in spans) / len(spans) if spans else None
