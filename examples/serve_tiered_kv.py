"""End-to-end serving driver: batched requests through the decode engine
with five-minute-rule KV-cache tiering.

Serves a reduced LM with continuous batching, then pauses sessions and
shows the tiering policy placing their KV blocks across DRAM/flash by
observed reuse interval, and resumes them transparently — including the
async-prefetch restore path overlapping the flash fetch with decode on
the platform's deterministic virtual clock.

The whole hierarchy is *declared*: a `HierarchySpec` (one host, static
seconds-scale thresholds, virtual clock, 5ms modeled decode step)
compiles into the platform, and the engine is a capability from its
facade — no clock/policy/store threading.

  PYTHONPATH=src python examples/serve_tiered_kv.py [--arch gemma-2b]
"""
import argparse
import sys
import pathlib
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1] / "src"))

import jax
import numpy as np

from repro.configs import ARCHS, get_config
from repro.models import model as M
from repro.parallel.sharding import single_device_rules
from repro.platform import HierarchySpec, HostDecl, Platform, PolicyDecl
from repro.serving.engine import Request


def main():
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma-2b", choices=ARCHS)
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--max-new", type=int, default=12)
    args = ap.parse_args()

    cfg = get_config(args.arch, reduced=True)
    rules = single_device_rules()
    params, _ = M.init_params(jax.random.PRNGKey(0), cfg)

    # the hierarchy, declared: one host, seconds-scale static
    # thresholds, deterministic virtual clock, 5ms modeled decode step
    spec = HierarchySpec(
        hosts=(HostDecl(),),
        policy=PolicyDecl.static(tau_hot=0.05, tau_be=1.0,
                                 ema_alpha=1.0),
        step_time=5e-3)
    platform = Platform.compile(spec)
    clock = platform.clock
    eng = platform.engine(cfg, params, rules, max_slots=4, max_len=64)

    rng = np.random.default_rng(0)
    reqs = [Request(rid=f"session-{i}",
                    prompt=rng.integers(1, cfg.vocab, 8).astype(np.int32),
                    max_new=args.max_new)
            for i in range(args.requests)]

    t0 = time.time()
    done = eng.run(reqs)
    dt = time.time() - t0
    toks = sum(len(r.generated) for r in done)
    print(f"[serve] {len(done)}/{len(reqs)} requests, {toks} tokens in "
          f"{dt:.1f}s ({toks/dt:.1f} tok/s on "
          f"{jax.devices()[0].device_kind}, compiling included), "
          f"{eng.steps} batched decode steps")
    for r in done[:3]:
        print(f"  {r.rid}: {r.generated}")

    # --- session pause/resume through the tiered store -------------------
    print("\n[tiering] pausing two sessions; hot one re-accessed quickly,"
          " cold one left idle")
    r0, r1 = done[0], done[1]
    eng.lengths[:] = 0
    eng.live[:] = False
    eng.slot_req.clear()
    eng.admit(r0)
    eng.admit(r1)
    tier_a = eng.pause(r0.rid)
    tier_b = eng.pause(r1.rid)
    print(f"  paused {r0.rid} -> {tier_a.name}, {r1.rid} -> {tier_b.name}")
    # hot session comes back fast: promote on reuse
    eng.resume(r0.rid)
    eng.pause(r0.rid)
    clock.advance(1.2)                # cold session crosses tau_be
    # async restore: issue the prefetch, let modeled decode compute
    # overlap the flash fetch, then resume without stalling
    eng.prefetch(r1.rid)
    clock.advance(3 * 5e-3)           # three decode steps elsewhere
    eng.resume(r1.rid)
    tier_hot = eng.store.tier_of(("kv", r0.rid))
    print(f"  after reuse pattern: {r0.rid} KV on "
          f"{tier_hot.name if tier_hot else 'engine'}, "
          f"{r1.rid} resumed with {eng.kv_stall_time*1e3:.2f}ms total "
          f"restore stall (prefetch overlapped)")
    print("\n[tier stats]")
    print(platform.report())
    print("\n[runtime queues]")
    print(eng.store.runtime.report())


if __name__ == "__main__":
    main()
