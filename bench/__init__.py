"""The chip benchmark of the label owner's served path.

`python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace 0|1`
runs one cell of `BENCHMARK.json`. Everything that defines a cell is data
found by name: `bench/configs/<config>.json`, `bench/traffic/<mix>.json`,
`bench/limits/<cell>.json`, and one reader per metric in
`bench/metrics/<metric>.py`. PERF.md describes the cells and the metrics.
"""
