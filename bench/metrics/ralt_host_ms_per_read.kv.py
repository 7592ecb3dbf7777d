"""Self time of the RALT tracker's spans (`ralt/record`, `ralt/limits`,
`ralt/hot_mask`, with the host syncs they hold) per page read."""
from bench.lib.program_spans import self_ms_per_read


def read(run):
    return self_ms_per_read(run, lambda name: name.startswith("ralt/"))
