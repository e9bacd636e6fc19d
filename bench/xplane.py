"""Reduction of a profiler trace (`.xplane.pb`) to what the metrics read.

From the device plane (`/device:TPU:<n>`): the union of the intervals in
which an operation ran (busy time), each program run (the "XLA Modules"
line) and the operations that took most time. From the host planes: the
benchmark's own annotations (`bench.<name>`), which say what the host
was doing when each program ran and in each gap in which the device was
idle. The program's jitted functions are not told apart by name in the
trace, so a program run is known by the host call that launched it: the
decode step is what `bench.step` launches. The measured window is the
`bench.window` annotation.
"""
from __future__ import annotations

from collections import defaultdict
from typing import Dict, List, Optional, Tuple

DEVICE_PREFIX = "/device:TPU:"
PREFIX = "bench."
LAUNCH = "PJRT_LoadedExecutable_Execute"    # a program launch on the host
TOP = 10


def _union(intervals: List[Tuple[float, float]]):
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(intervals, t0: float, t1: float):
    return [(max(s, t0), min(e, t1)) for s, e in intervals
            if e > t0 and s < t1]


def _labels(anns: List[Tuple[float, float, str]], t0: float, t1: float,
            base: str):
    """Segments of [t0, t1] labelled by the innermost host annotation
    covering them (annotations nest); `base` where none does."""
    segs, stack, cur = [], [(t1, base)], t0
    for s, e, name in sorted(anns, key=lambda a: (a[0], -a[1])):
        s, e = max(s, t0), min(e, t1)
        if e <= s:
            continue
        while len(stack) > 1 and stack[-1][0] <= s:
            end, lab = stack.pop()
            if end > cur:
                segs.append((cur, end, lab))
                cur = end
        if s > cur:
            segs.append((cur, s, stack[-1][1]))
            cur = s
        stack.append((e, name))
    while stack:
        end, lab = stack.pop()
        end = min(end, t1)
        if end > cur:
            segs.append((cur, end, lab))
            cur = end
    return segs


def _overlap_by_label(gaps, segs) -> Dict[str, float]:
    """Seconds of `gaps` covered by each label (both lists sorted and
    disjoint)."""
    out: Dict[str, float] = defaultdict(float)
    i = j = 0
    while i < len(gaps) and j < len(segs):
        s = max(gaps[i][0], segs[j][0])
        e = min(gaps[i][1], segs[j][1])
        if e > s:
            out[segs[j][2]] += e - s
        if gaps[i][1] < segs[j][1]:
            i += 1
        else:
            j += 1
    return out


def _label_at(segs, points):
    """Label of the segment holding each of the sorted `points`."""
    out, j = [], 0
    for t in points:
        while j < len(segs) - 1 and segs[j][1] <= t:
            j += 1
        out.append(segs[j][2] if segs and segs[j][0] <= t < segs[j][1]
                   else "outside")
    return out


def reduce(path: str, device: int = 0) -> Optional[dict]:
    """Reduce one `.xplane.pb`; None when it holds no device plane."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    ops, modules, anns, launches = [], [], [], []
    dev_name = f"{DEVICE_PREFIX}{device}"
    for plane in pd.planes:
        if plane.name == dev_name:
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops += [(e.start_ns, e.start_ns + e.duration_ns,
                             e.name.split(" = ")[0]) for e in line.events]
                elif line.name == "XLA Modules":
                    modules += [(e.start_ns, e.start_ns + e.duration_ns)
                                for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(PREFIX):
                        anns.append((e.start_ns, e.start_ns + e.duration_ns,
                                     e.name[len(PREFIX):]))
                    elif e.name == LAUNCH:
                        launches.append(e.start_ns)
    if not ops:
        return None
    # the profiler's device clock can sit a millisecond or so off the
    # host's: line the k-th program run up with the k-th launch on the
    # host when the two counts agree
    modules.sort()
    launches.sort()
    paired = len(launches) == len(modules)
    offset = 0.0
    if paired:
        d = sorted(m[0] - h for m, h in zip(modules, launches))
        offset = d[len(d) // 2]
    ops = sorted((s - offset, e - offset, n) for s, e, n in ops)
    modules = [(s - offset, e - offset) for s, e in modules]
    win = [a for a in anns if a[2] == "window"]
    if win:
        t0, t1 = win[0][0], win[0][1]
    else:
        t0, t1 = ops[0][0], max(e for _, e, _ in ops)
    busy = _union(_clip([(s, e) for s, e, _ in ops], t0, t1))
    gaps, cur = [], t0
    for s, e in busy:
        if s > cur:
            gaps.append((cur, s))
        cur = e
    if cur < t1:
        gaps.append((cur, t1))
    segs = _labels([a for a in anns if a[2] != "window"], t0, t1,
                   "harness")
    idle = _overlap_by_label(gaps, segs)

    # each program run under the host call that launched it (a run can
    # start after that call returned: a splice is not waited for), and
    # each op under its program's label
    labels = _label_at(segs, launches if paired
                       else [(s + e) / 2 for s, e in modules])
    runs: Dict[str, List[float]] = defaultdict(lambda: [0.0, 0])
    for (s, e), lab in zip(modules, labels):
        if lab != "outside":
            runs[lab][0] += (e - s) * 1e-9
            runs[lab][1] += 1
    by_op: Dict[str, float] = defaultdict(float)
    inside = [o for o in ops if t0 <= o[0] and o[1] <= t1]
    loose = _label_at(segs, [(s + e) / 2 for s, e, _ in inside])
    j = 0
    for (s, e, name), lab in zip(inside, loose):
        while j < len(modules) and modules[j][1] <= s:
            j += 1
        if j < len(modules) and modules[j][0] <= s:
            lab = labels[j]
        by_op[f"{lab}/{name}"] += (e - s) * 1e-9
    top = sorted(by_op.items(), key=lambda kv: -kv[1])[:TOP]
    idle_top = sorted(idle.items(), key=lambda kv: -kv[1])[:TOP]
    return {"window_s": (t1 - t0) * 1e-9,
            "busy_s": sum(e - s for s, e in busy) * 1e-9,
            "offset_s": offset * 1e-9,
            "programs_by_host": {k: {"seconds": v[0], "count": v[1]}
                                 for k, v in runs.items()},
            "top_ops": [[k, v] for k, v in top],
            "idle_by_host": [[k, v * 1e-9] for k, v in idle_top]}
