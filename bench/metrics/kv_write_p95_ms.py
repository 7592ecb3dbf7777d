"""95th percentile of the wall time of each page write, from the call
to TieredKVCache.write_page until the pool it changed is ready."""
from bench.lib.readers import p95_ms


def read(run):
    return p95_ms(run.samples["write_s"])
