"""`host_us_per_tok` (bench/metrics/host_us_per_tok.py) in the long-generation cells,
where the arena is full and it moves `tok_per_s`."""
from bench import spec

read = spec.reader("host_us_per_tok").read
