"""Mean duration of the program's `kv/write_page` spans: a write's host
part, without the driver's wait for the pool it changed."""
from bench.lib.program_spans import mean_ms


def read(run):
    return mean_ms(run, "kv/write_page")
