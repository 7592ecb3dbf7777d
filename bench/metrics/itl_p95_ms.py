"""95th percentile of all gaps between consecutive output tokens of
the same request, pooled over the window."""
from bench.lib.readers import p95_ms


def read(run):
    return p95_ms(run.samples["itl_s"])
