"""Run one benchmark cell once.

  python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout that holds BENCHMARK.json, bench/ and the
program (src/). It needs the chip: without a TPU, or with fewer chips
than the cell asks for, it exits 2 and prints no result. The last line
of standard output is the result (JSON); the numbers compared for
`correct` are the last lines of standard error.
"""
from __future__ import annotations

import argparse
import json
import sys

from boot import ROOT


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()

    import jax
    from bench import harness

    bench = harness.Bench(ROOT)
    chips = bench.cell(args.workload)["chips"]
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < chips:
        print(f"bench/run.py: the cell needs {chips} TPU chip(s); JAX "
              f"found {len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 2
    res = harness.run(ROOT, args.workload, args.seed, args.seconds,
                      bool(args.trace))
    line = harness.report(bench, res, bool(args.trace))
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
