"""95th percentile, over the requests the engine admitted in the traced
stretch, of the engine's own first-token instant minus its admission
instant (same request id): the wave's prompt, without the queue wait."""
from bench.lib import program_spans


def read(run):
    return program_spans.first_token_p95_ms(run)
