"""`arena_step_roofline` (bench/metrics/arena_step_roofline.py) in the long-generation cells,
where the arena is full and it moves `tok_per_s`."""
from bench import spec

read = spec.reader("arena_step_roofline").read
