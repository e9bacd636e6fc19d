"""Readings that a cell's correctness limits are set from: for each
seed, one run of the cell's own traffic and sample (a short window at
the cell's load), the numbers the program gives, and the numbers of the
control, the float32 reference computed in fp8 (the precision below
the served bf16) and put in the program's place, each judged by the
cell's result line.

  python3 bench/control.py --workload <cell> --seeds 1,2,3 --seconds 30

Prints one JSON line per seed. Needs the chip; not part of a
benchmark run.
"""
from __future__ import annotations

import argparse
import json
import sys

from boot import ROOT


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args()

    import jax
    from bench import harness

    if jax.devices()[0].platform != "tpu":
        print("bench/control.py: needs a TPU", file=sys.stderr)
        return 2
    bench = harness.Bench(ROOT)
    controls = ("fp8",)
    for seed in (int(s) for s in args.seeds.split(",")):
        res = harness.measure(bench, args.workload, seed, args.seconds,
                              False)
        harness.check(res, controls)
        line = harness.result_line(bench, res, False)
        row = {"workload": args.workload, "seed": seed,
               "program": {"correct": line["correct"], **line["checks"]},
               "altered_gap": max((c["altered_gap"] for c in res["checks"]
                                   if c["altered_gap"] is not None),
                                  default=None)}
        for c in controls:
            ctrl = harness.result_line(bench, harness.as_control(res, c),
                                       False)
            row[c] = {"correct": ctrl["correct"], **ctrl["checks"]}
        row.update(sample=[{k: v for k, v in c.items() if k != "control"}
                           for c in res["checks"]],
                   check_s=res["check_s"], compiles=res["compiles"],
                   memory_peak=res["memory_peak"])
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
