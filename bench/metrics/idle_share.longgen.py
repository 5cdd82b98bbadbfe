"""`idle_share` (bench/metrics/idle_share.py) in the long-generation cells,
where the arena is full and it moves `tok_per_s`."""
from bench import spec

read = spec.reader("idle_share").read
