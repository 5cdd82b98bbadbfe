"""`itl_p95_ms` (bench/metrics/itl_p95_ms.py) in the long-generation
cells, where the arena is full, every step is of the same rows and the
gaps spread far less than in the chat cells: a bound of its own."""
from bench import spec

read = spec.reader("itl_p95_ms").read
