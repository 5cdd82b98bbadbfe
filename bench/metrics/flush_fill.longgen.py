"""`flush_fill` (bench/metrics/flush_fill.py) in the long-generation cells,
where the arena is full and it moves `tok_per_s`."""
from bench import spec

read = spec.reader("flush_fill").read
