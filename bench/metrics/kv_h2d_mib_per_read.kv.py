"""Host-to-device MiB per page read: the `h2d_bytes` counter's change
across each `kv/read_pages` span (slow pages fetched, and pages promoted
by the flushes and sweeps the read triggers), over the reads."""
from bench.lib import program_spans


def read(run):
    return program_spans.h2d_mib_per_read(run)
