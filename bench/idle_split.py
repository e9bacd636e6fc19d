"""Where a cell's device-idle time goes inside the program: one traced
window of the cell through the harness, with the program's spans on
(`repro.obs.trace.enable_spans`) inside the window, the engine's
`d2h_bytes` counter read at the window's ends, and the spans reduced by
`bench/spans.py` from the same trace before its directory is deleted.

  python3 bench/idle_split.py --workload <cell> --seeds 1,2 \\
      --seconds 50 --spans 1,0

Each seed runs once per value of `--spans` (0 gives the same traced run
with the spans off: the cost of having them on). Prints the idle under
each span name and one JSON line per run. Needs the chip; not part of a
benchmark run.
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import sys
from typing import Tuple

from boot import ROOT


def traced_run(bench, workload: str, seed: int, seconds: float,
               spans_on: bool) -> Tuple[dict, dict]:
    """The harness's traced run of one cell and its check, with the
    program's spans on (or off) inside the window, the engine's
    `d2h_bytes` read at the window's ends, and the spans reduced from
    the trace before its directory is deleted."""
    from bench import harness, spans, xplane
    from repro.obs.trace import enable_spans
    got: dict = {"d2h_bytes": None, "spans": None}
    window, reduce_dir = harness.window, xplane.reduce_dir

    def counted_window(sched, *args):
        counters = sched.engine.counters
        before = counters["d2h_bytes"]
        enable_spans(spans_on)
        try:
            return window(sched, *args)
        finally:
            enable_spans(False)
            got["d2h_bytes"] = counters["d2h_bytes"] - before

    def reduce_both(directory):
        reduced = reduce_dir(directory)
        found = sorted(glob.glob(os.path.join(directory, "**",
                                              "*.xplane.pb"),
                                 recursive=True), key=os.path.getmtime)
        if reduced is not None and found:
            got["spans"] = spans.reduce(found[-1], reduced["offset_s"])
        return reduced

    harness.window, xplane.reduce_dir = counted_window, reduce_both
    try:
        res = harness.measure(bench, workload, seed, seconds, True)
    finally:
        harness.window, xplane.reduce_dir = window, reduce_dir
    harness.check(res)
    return res, got


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--spans", default="1")
    args = ap.parse_args()

    import jax
    from bench import harness, spans
    if jax.devices()[0].platform != "tpu":
        print("bench/idle_split.py: needs a TPU", file=sys.stderr)
        return 2
    bench = harness.Bench(ROOT)
    for seed in (int(s) for s in args.seeds.split(",")):
        for on in (bool(int(v)) for v in args.spans.split(",")):
            res, got = traced_run(bench, args.workload, seed, args.seconds,
                                  on)
            line = harness.report(bench, res, True)
            run = res["run"]
            row = {"seed": seed, "spans": on, "correct": line["correct"],
                   "metrics": {k: v["value"] for k, v in
                               line["metrics"].items()},
                   "breakdown": line.get("breakdown"),
                   "busy_s": line["device"].get("busy_s"),
                   "window_s": line["device"].get("window_s"),
                   "d2h_kb_per_token": spans.d2h_kb_per_token(
                       got["d2h_bytes"], run.tokens_in_window()),
                   "decode_steps": res["counters"]["decode_steps"]}
            sp = got["spans"]
            if on and sp is not None:
                tot = sp["totals"]
                harness.say("[trace] device idle under each program "
                            "span, s: " + spans.split_line(tot))
                row.update(step_idle_ms=spans.step_idle_ms(sp["instances"]),
                           tick_idle_ms=spans.tick_idle_ms(sp["instances"]),
                           window_idle_s=sp["window_idle_s"],
                           idle_by_span={k: tot[k] for k in sorted(tot)})
            print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
