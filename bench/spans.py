"""Device-idle time under the program's own spans.

The served path marks each phase of a scheduler tick and of the
engine's calls with a profiler annotation `repro.<name>`
(`repro.obs.trace.span`, while spans are on). This module lines those
host intervals up with the device's busy intervals from the same trace
(`.xplane.pb`), with the clock offset that `xplane.reduce` found, and
gives each span instance its device-idle seconds: the part of its
interval, clipped to `bench.window`, in which no operation ran. Its
self idle leaves out what its child spans cover, so the self idle of
all spans adds up to no more than the window's idle time.
The harness reduces them in every traced run (`harness.reduce_trace`).
"""
from __future__ import annotations

import bisect
import statistics
from collections import defaultdict
from typing import Dict, List, Optional

from bench import xplane

PREFIX = "repro."
WINDOW = xplane.PREFIX + "window"
STEP = "engine.step"
TICK = "scheduler.tick"
# the engine calls that a tick's own idle leaves out
ENGINE = (STEP, "engine.admit")


def read(path: str, offset_s: float, device: int = 0):
    """(`repro.*` host events as (start, end, name, thread line),
    device busy intervals, window start, window end), in ns on the
    host's clock; None without a device plane or a window."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    events, ops, win = [], [], None
    for plane in pd.planes:
        if plane.name == f"{xplane.DEVICE_PREFIX}{device}":
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops += [(e.start_ns, e.start_ns + e.duration_ns)
                            for e in line.events]
        elif plane.name.startswith("/host:"):
            for k, line in enumerate(plane.lines):
                for e in line.events:
                    end = e.start_ns + e.duration_ns
                    if e.name.startswith(PREFIX):
                        events.append((e.start_ns, end,
                                       e.name[len(PREFIX):],
                                       (plane.name, k)))
                    elif e.name == WINDOW:
                        win = (e.start_ns, end)
    if not ops or win is None:
        return None
    off = offset_s * 1e9
    busy = xplane._union(xplane._clip([(s - off, e - off) for s, e in ops],
                                      *win))
    return events, busy, win[0], win[1]


def idle_under(events, busy, t0: float, t1: float) -> List[dict]:
    """Each span instance inside [t0, t1] (clipped to it) with its
    device-idle seconds (`idle`), those not under a child span
    (`self_idle`) and the index of its parent instance. Spans nest
    within a thread line; times in ns, seconds out."""
    gaps, cur = [], t0
    for s, e in busy:
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, e)
    if cur < t1:
        gaps.append((cur, t1))
    starts = [s for s, _ in gaps]
    cum = [0.0]
    for s, e in gaps:
        cum.append(cum[-1] + e - s)

    def idle_before(t):
        i = bisect.bisect_right(starts, t)    # gaps that start by t
        if not i:
            return 0.0
        s, e = gaps[i - 1]
        return cum[i - 1] + min(t, e) - s

    out: List[dict] = []
    stacks: Dict[object, list] = defaultdict(list)
    for s, e, name, line in sorted(events, key=lambda v: (v[0], -v[1])):
        s, e = max(s, t0), min(e, t1)
        if e <= s:
            continue
        stack = stacks[line]
        while stack and out[stack[-1]]["t1"] <= s:
            stack.pop()
        parent = stack[-1] if stack else None
        idle = (idle_before(e) - idle_before(s)) * 1e-9
        out.append({"name": name, "t0": s, "t1": e, "idle": idle,
                    "self_idle": idle, "parent": parent})
        if parent is not None:
            out[parent]["self_idle"] -= idle
        stack.append(len(out) - 1)
    return out


def totals(instances: List[dict]) -> Dict[str, dict]:
    """Per span name: instances, idle seconds and self idle seconds."""
    got: Dict[str, dict] = defaultdict(
        lambda: {"count": 0, "idle_s": 0.0, "self_idle_s": 0.0})
    for r in instances:
        t = got[r["name"]]
        t["count"] += 1
        t["idle_s"] += r["idle"]
        t["self_idle_s"] += r["self_idle"]
    return dict(got)


def reduce(path: str, offset_s: float) -> Optional[dict]:
    """Span instances and per-name totals of one trace; None without a
    device plane or a window."""
    got = read(path, offset_s)
    if got is None:
        return None
    events, busy, t0, t1 = got
    inst = idle_under(events, busy, t0, t1)
    return {"instances": inst, "totals": totals(inst),
            "window_idle_s": (t1 - t0 - sum(e - s for s, e in busy))
            * 1e-9}


def step_idle_ms(instances: List[dict]) -> Optional[float]:
    """Median over `engine.step` spans of the device-idle ms inside."""
    got = [r["idle"] for r in instances if r["name"] == STEP]
    return statistics.median(got) * 1e3 if got else None


def tick_idle_ms(instances: List[dict]) -> Optional[float]:
    """Median over the ticks that hold a decode step of the device-idle
    ms inside the tick but outside its `engine.step` and `engine.admit`
    calls."""
    own = {i: r["idle"] for i, r in enumerate(instances)
           if r["name"] == TICK}
    stepped = set()
    for r in instances:
        tick = _ancestor(instances, r, TICK) if r["name"] in ENGINE \
            else None
        if tick is not None:
            own[tick] -= r["idle"]
            if r["name"] == STEP:
                stepped.add(tick)
    got = [own[i] for i in stepped]
    return statistics.median(got) * 1e3 if got else None


def _ancestor(instances, r, name) -> Optional[int]:
    p = r["parent"]
    while p is not None and instances[p]["name"] != name:
        p = instances[p]["parent"]
    return p


def d2h_kb_per_token(d2h_bytes: Optional[int], tokens: int
                     ) -> Optional[float]:
    """KiB the engine copied to the host per token in the window."""
    if d2h_bytes is None or not tokens:
        return None
    return d2h_bytes / tokens / 1024


def split_line(tot: Dict[str, dict]) -> str:
    """`name s` per span name, by self idle, the largest first."""
    return ", ".join(f"{k} {v['self_idle_s']:.4f}" for k, v in
                     sorted(tot.items(), key=lambda kv: -kv[1]
                            ["self_idle_s"]))
