"""Readings that set a cell's correctness limit (not run by the benchmark).

    python3 bench/control.py --workload <cell> --seeds 11,12,13 --seconds 10

For each seed, in one process: one sound run of the cell, with the widest
logit gap of what it served and, on the same sample, that of the reference
one precision below the configuration's put in the program's place
(bench/reference.py `control_quant`: w8a8 for bf16). One JSON line per
seed, then a summary line. PERF.md records the readings and the limits set from
them.
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
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    from bench import harness, reference, spec

    cell = spec.cell(spec.benchmark(), args.workload)
    import jax

    if jax.devices()[0].platform != "tpu":
        print("control: needs a TPU", file=sys.stderr)
        return 2
    harness.enable_compile_cache()
    rows = []
    for seed in (int(s) for s in args.seeds.split(",")):
        rep: dict = {}
        out = harness.run(cell, seed, args.seconds, False,
                          time.perf_counter(),
                          control=reference.control_quant(cell["conf"]),
                          report=rep)
        row = {"seed": seed, "sound_gap": rep["gap"],
               "control_gap": rep.get("control_gap"),
               "positions": rep["positions"], "agree": rep["agree"],
               "overrides": rep["overrides"], "correct": out["correct"],
               "metrics": out["metrics"]}
        rows.append(row)
        print("CONTROL " + json.dumps(row), flush=True)
    summary = {k: [r.get(k) for r in rows]
               for k in ("seed", "sound_gap", "control_gap")}
    print("CONTROL_SUMMARY " + json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
