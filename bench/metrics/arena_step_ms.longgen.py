"""`arena_step_ms` (bench/metrics/arena_step_ms.py) in the long-generation cells,
where the arena is full and it moves `itl_p95_ms.longgen`."""
from bench import spec

read = spec.reader("arena_step_ms").read
