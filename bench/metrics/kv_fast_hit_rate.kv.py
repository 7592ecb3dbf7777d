"""Share of the traced stretch's page reads served from the HBM tier (the
change of SimClock's fast_hits over fast_hits + slow_hits)."""


def read(run):
    c = run.counters
    n = c["fast_hits"] + c["slow_hits"]
    return c["fast_hits"] / n * 100.0 if n else None
