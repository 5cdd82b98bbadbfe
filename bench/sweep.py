"""The knee sweep of a cell (not run by the benchmark).

    python3 bench/sweep.py --workload <cell> --rates 10,15,20 --seconds 10

Runs the cell once per session rate, in one process, and prints one line
per rate: the admission FIFO depth at the window's open and close, the
sessions failed, and the end-to-end metrics. The knee is the highest rate
at which the FIFO does not grow through the window and no session fails;
a cell's mix offers 4/5 of it. PERF.md records each sweep.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (CHECKOUT, os.path.join(CHECKOUT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args(argv)

    from bench import harness, spec

    cell = spec.cell(spec.benchmark(), args.workload)
    import jax

    if jax.devices()[0].platform != "tpu":
        print("sweep: needs a TPU", file=sys.stderr)
        return 2
    harness.enable_compile_cache()
    for rate in (float(r) for r in args.rates.split(",")):
        traffic = dict(cell["traffic"], arrivals=dict(
            cell["traffic"]["arrivals"], sessions_per_s=rate))
        rep: dict = {}
        out = harness.run(dict(cell, traffic=traffic), args.seed,
                          args.seconds, False, time.perf_counter(),
                          report=rep)
        print("SWEEP " + json.dumps({
            "rate": rate, "fifo_open": rep["fifo_open"],
            "fifo_close": rep["fifo_close"], "failed": rep["failed"],
            "attempted": rep["attempted"], "correct": out["correct"],
            "metrics": {k: v["value"] for k, v in out["metrics"].items()}}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
