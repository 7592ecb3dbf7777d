"""Host work per engine step: mean over the stretch's `engine/step` spans
of the step's duration minus its `engine/sync` child (the wait for the
device), from the program's own spans."""
from bench.lib import program_spans


def read(run):
    return program_spans.engine_host_ms_per_step(run)
