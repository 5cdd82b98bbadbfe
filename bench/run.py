"""Run one cell of BENCHMARK.json on the chip.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace 0|1

From the root of a checkout. Progress goes to stdout, one line per part of
set-up and per reading; the last line of stdout is the result, one JSON
object. The numbers the correctness check compared, each beside its
limit, are the last lines of stderr and the last key of the result.

Without a TPU, or with fewer chips than the cell asks for, it exits 2 and
prints no result; on any other failure it exits nonzero, also without one.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()     # set-up is timed from here

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (CHECKOUT, os.path.join(CHECKOUT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be a non-negative whole number")

    from bench import harness, spec

    cell = spec.cell(spec.benchmark(), args.workload)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell["chips"]:
        print(f"bench: {args.workload} needs {cell['chips']} TPU chip(s); "
              f"JAX found {len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 2
    cache = harness.enable_compile_cache()
    dev = devices[0]
    print(f"device: {dev.platform} {dev.device_kind} x{len(devices)}; "
          f"compile cache {cache}; cell {args.workload}, seed {args.seed}, "
          f"{args.seconds:g} s window, trace {args.trace}", flush=True)
    out = harness.run(cell, args.seed, args.seconds, bool(args.trace),
                      T_PROCESS)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
