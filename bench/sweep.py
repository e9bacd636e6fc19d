"""Find a cell's knee: the highest arrival rate at which the admission
queue does not grow over the window. One process, one set-up; each seed
and rate gets a window of the cell's traffic at that rate, then the
scheduler drains before the next.

  python3 bench/sweep.py --workload <cell> --seeds 1,2 --seconds 50 \\
      --rates 1.0,1.5,2.0

Prints one JSON line per seed and rate. Needs the chip; not part of a
benchmark run.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time

from boot import ROOT


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args()

    import jax
    import numpy as np
    from bench import harness
    from bench.traffic import generator

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print("bench/sweep.py: needs a TPU", file=sys.stderr)
        return 2
    bench = harness.Bench(ROOT)
    cell = bench.cell(args.workload)
    cfg, mix = bench.config(cell["config"]), bench.traffic(cell["traffic"])
    sv = cfg["serving"]
    seeds = [int(s) for s in args.seeds.split(",")]
    _, sched = harness.build(cfg, seeds[0], dev)
    probe = harness.Probe(sched.engine, annotate=False)
    harness.warm_up(sched, cfg, mix, np.random.default_rng(seeds[0]))
    k = 0
    for seed in seeds:
        for rate in (float(r) for r in args.rates.split(",")):
            k += 1
            m = dict(mix, rate_per_s=rate)
            sessions = [dataclasses.replace(s, sid=f"r{k}-{s.sid}")
                        for s in generator.schedule(
                            m, seed, args.seconds, cfg["vocab_size"],
                            sv["max_len"])]
            before = dict(sched.metrics)
            records, tick_walls, t_open, t_close = harness.window(
                sched, sessions, args.seconds, sv["step_time_s"], probe)
            run = harness.Run(args.workload, cfg, m, args.seconds, t_open,
                              t_close, records, tick_walls, probe.spans,
                              probe.steps, 0.0, dev.device_kind, None)
            ttft = run.first_turn_waits("token")
            walls = sorted(tick_walls.values())
            row = {"seed": seed, "rate": rate, "arrived": len(records),
                   "max_tick_gap_ms": max(np.diff(walls), default=0) * 1e3,
                   "late_max_ms": max((r.submitted - r.arrival
                                       for r in records), default=0) * 1e3,
                   "tokens_per_s": run.tokens_in_window() / args.seconds,
                   "ttft_p50_ms": harness.percentile(ttft, 50) * 1e3,
                   "ttft_p90_ms": harness.percentile(ttft, 90) * 1e3,
                   "itl_p95_ms": (harness.percentile(run.token_gaps(), 95)
                                  or 0) * 1e3,
                   "queue_each_5s": [run.queue_at(t) for t in np.arange(
                       t_open + 5, t_close + 1e-9, 5.0)],
                   "counts": {c: sched.metrics[c] - before[c] for c in
                              ("decode_steps", "idle_ticks", "admissions",
                               "pauses", "resumes")}}
            print(json.dumps(row), flush=True)
            # drain, then drop the finished sessions' KV blobs
            t = time.perf_counter()
            while sched.pending_work() and time.perf_counter() - t < 240:
                sched.tick()
            for r in records:
                key = ("kv", r.session.sid)
                if r.job.state == "done" and \
                        sched.engine.store.tier_of(key):
                    sched.engine.store.delete(key)
    return 0


if __name__ == "__main__":
    sys.exit(main())
