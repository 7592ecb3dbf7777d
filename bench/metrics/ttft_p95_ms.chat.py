"""`ttft_p95_ms` read per layer, over the requests admitted in the traced
stretch: in the chat cell a request that just misses a lockstep wave waits
for the whole next one, so the p95 over a run falls in two modes by timing
alone, too far apart for an end-to-end bound."""
from bench.metrics.ttft_p95_ms import read  # noqa: F401
