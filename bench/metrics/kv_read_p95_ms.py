"""95th percentile of the wall time of each page-set read, from the call
to TieredKVCache.read_pages until its pages are ready on the device."""
from bench.lib.readers import p95_ms


def read(run):
    return p95_ms(run.samples["read_s"])
