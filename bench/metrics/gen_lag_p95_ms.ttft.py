"""95th percentile of how late the load generator submitted each
request after its due time (its own lateness, not queueing)."""
from bench.lib.readers import p95_ms


def read(run):
    return p95_ms(run.samples.get("gen_lag_s", []))
