"""`queue_wait_ms_p50` (bench/metrics/queue_wait_ms_p50.py) in the long-generation cells,
where the arena is full and it moves `itl_p95_ms.longgen`."""
from bench import spec

read = spec.reader("queue_wait_ms_p50").read
