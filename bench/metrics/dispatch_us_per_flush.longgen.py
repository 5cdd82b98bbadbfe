"""`dispatch_us_per_flush` (bench/metrics/dispatch_us_per_flush.py) in the
long-generation cells, where the arena is full and it moves `tok_per_s`."""
from bench import spec

read = spec.reader("dispatch_us_per_flush").read
