"""`serve_mfu` (bench/metrics/serve_mfu.py) in the long-generation cells,
where the arena is full and it moves `tok_per_s`."""
from bench import spec

read = spec.reader("serve_mfu").read
