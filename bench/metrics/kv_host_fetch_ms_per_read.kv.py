"""Self time of the tier manager's `kv/host_fetch` spans (the slow pages'
host-to-device copies) per page read."""
from bench.lib.program_spans import self_ms_per_read


def read(run):
    return self_ms_per_read(run, lambda name: name == "kv/host_fetch")
