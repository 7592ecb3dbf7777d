"""On-chip benchmark of the serving half: one command, driven by data.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

`BENCHMARK.json` at the checkout root lists the cells.  Everything the
harness loads for a cell is found by name:

    bench/configs/<config>.json     sizes, with their published source
    bench/traffic/<traffic>.json    traffic parameters; names its generator
                                    (bench/traffic/<generator>.py) and the
                                    driver of the entry it drives
                                    (bench/drivers/<driver>.py)
    bench/metrics/<metric>.py       one reader per metric
    bench/reference/<family>.py     plain float32 reference and weight maker

A new configuration, traffic mix or metric is new files plus entries in
`BENCHMARK.json`; no existing file changes.
"""
