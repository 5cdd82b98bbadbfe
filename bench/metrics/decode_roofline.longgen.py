"""`decode_roofline` (bench/metrics/decode_roofline.py) in the long-generation cells,
where the arena is full and it moves `tok_per_s`."""
from bench import spec

read = spec.reader("decode_roofline").read
