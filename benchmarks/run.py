"""Benchmark harness: one artifact per paper table/figure + the dry-run
roofline grid. `python -m benchmarks.run [--full] [--skip-roofline]`.

Each paper artifact asserts its acceptance anchors (numbers quoted in the
paper text), so a green run IS the reproduction check.
"""
from __future__ import annotations

import argparse
import sys
import time
import pathlib

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1] / "src"))
sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))


def _render(name, rows, note, show=6):
    print(f"\n=== {name} " + "=" * max(1, 66 - len(name)))
    if rows:
        keys = list(rows[0].keys())
        print(" | ".join(f"{k}" for k in keys))
        for r in rows[:show]:
            print(" | ".join(
                f"{v:.4g}" if isinstance(v, float) else str(v)
                for v in r.values()))
        if len(rows) > show:
            print(f"... ({len(rows)} rows total)")
    print(f"--> {note}")


def main():
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="long simulator runs (more ops)")
    ap.add_argument("--skip-roofline", action="store_true")
    args = ap.parse_args()
    quick = not args.full

    from benchmarks import paper_figs as pf

    t0 = time.time()
    artifacts = [
        ("Fig. 3  SSD peak IOPS vs block size", pf.fig3_iops, {}),
        ("Table II  IOPS sensitivity (N_CH/N_NAND/tau_CMD)",
         pf.table2_sensitivity, {}),
        ("Fig. 4  break-even interval stacks", pf.fig4_breakeven, {}),
        ("Table IV  tail-latency tiers <-> rho_max", pf.table4_rho_tiers,
         {}),
        ("Fig. 5  constraint-aware break-even", pf.fig5_constraints, {}),
        ("Fig. 6  workload-aware provisioning", pf.fig6_provisioning, {}),
        ("Fig. 7  MQSim-Next vs analytic model", pf.fig7_sim_vs_model,
         {"quick": quick}),
        ("Fig. 8  SSD-resident KV store throughput", pf.fig8_kvstore, {}),
        ("Fig. 10  two-stage progressive ANN", pf.fig10_ann,
         {"quick": quick}),
        ("Beyond-paper: TCO + CXL 4-tier ladder (paper §VIII)",
         pf.tco_ladder, {}),
        ("Beyond-paper: async-prefetch serving stall (runtime)",
         pf.serving_async, {"quick": quick}),
    ]
    failures = []
    for name, fn, kw in artifacts:
        t = time.time()
        try:
            rows, note = fn(**kw)
            _render(name, rows, note)
            print(f"    [{time.time()-t:.1f}s]")
        except AssertionError as e:
            failures.append((name, e))
            print(f"\n=== {name}\n--> ANCHOR FAILED: {e}")
        except Exception as e:
            failures.append((name, e))
            print(f"\n=== {name}\n--> ERROR: {type(e).__name__}: {e}")

    if not args.skip_roofline:
        print("\n=== Dry-run roofline grid " + "=" * 42)
        try:
            from benchmarks import roofline_report
            res = roofline_report.load("single")
            if res:
                print(roofline_report.single_pod_table(res))
                multi = roofline_report.load("multi")
                if multi:
                    print("\n-- multi-pod (2x16x16) --")
                    print(roofline_report.multi_pod_table(multi))
                vt = roofline_report.variant_table()
                if vt:
                    print("\n-- hillclimb variants (vs baseline) --")
                    print(vt)
            else:
                print("(no results/dryrun/*.json yet — run "
                      "`python -m repro.launch.dryrun --all`)")
        except Exception as e:
            print(f"roofline report unavailable: {e}")

    print(f"\n{'='*72}\n{len(artifacts)-len(failures)}/{len(artifacts)} "
          f"paper artifacts reproduced in {time.time()-t0:.0f}s")
    for name, e in failures:
        print(f"  FAILED: {name}: {e}")
    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    main()
