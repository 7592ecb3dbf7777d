"""Device time per call of the jitted decode step (its module events in
the trace)."""
from bench.lib.readers import module_ms_per_call


def read(run):
    return module_ms_per_call(run, r"^jit_decode_step\b")
