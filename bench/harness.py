"""One run of one benchmark cell: set-up, a measured window of open-loop
traffic through the served path, the metrics, and the correctness check.

Everything that belongs to one configuration, traffic mix or metric is
found by name under the checkout's `bench/` directory:

  BENCHMARK.json                 cells, metrics, configuration files
  bench/configs/<config>.json    sizes, serving geometry, source, "arch"
  bench/arch/<arch>.py           layout, float32 forward, work counts and
                                 the program's ModelConfig
  bench/traffic/<mix>.json       parameters of the one generator
  bench/metrics/<metric>.py      `read(run) -> float | None`
  bench/limits/<cell>.json       the limit of each number compared

The timed entry is `ContinuousScheduler.tick()` on the scheduler that
`Platform.compile(spec).scheduler(...)` builds. The harness submits each
session when its wall-clock arrival is due, ticks the scheduler, paces
ticks in which no slot decodes at the spec's declared step time, and
stamps every token on the wall clock when the engine call that made it
returns (that call ends on the host, so the token is there). It keeps
the logits that the timed path produced for a set of requests drawn
from the seed, and compares them, once the window has closed, with the
benchmark's float32 reference (`bench/reference.py`).

A traced run (`--trace 1`) also turns the program's own spans on for
the window (`repro.obs.trace.enable_spans`), lines them up with the
device trace (`bench/spans.py`), and takes the change of each of the
engine's counters over the window, for the per-layer readers. An
untraced run does neither.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import gc
import glob
import importlib.util
import json
import math
import os
import pathlib
import shutil
import sys
import tempfile
import time
from collections import deque
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

from bench import arch, reference, spans, xplane
from bench.traffic import generator

# the numbers compared for `correct`, each against its limit
NUMBERS = ("logit_err", "token_gap")


# ------------------------------------------------------------- by name
class Bench:
    """The benchmark's data, found by name under `root`."""

    def __init__(self, root: pathlib.Path):
        self.root = pathlib.Path(root)
        self.spec = json.loads((self.root / "BENCHMARK.json").read_text())

    def _file(self, *parts) -> pathlib.Path:
        return self.root.joinpath("bench", *parts)

    def cell(self, name: str) -> dict:
        for w in self.spec["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")

    def config(self, name: str) -> dict:
        """The configuration file's dict, with the directory its `"arch"`
        is found in (`arch_dir`: this root's bench/arch); an unknown
        architecture is an error here."""
        entry = next(c for c in self.spec["configs"] if c["name"] == name)
        cfg = json.loads((self.root / entry["file"]).read_text())
        cfg["arch_dir"] = str(self._file("arch"))
        arch.of(cfg)
        return cfg

    def traffic(self, name: str) -> dict:
        return json.loads(self._file("traffic", f"{name}.json").read_text())

    def limits(self, cell: str) -> dict:
        return json.loads(self._file("limits", f"{cell}.json").read_text())

    def reader(self, metric: str) -> Callable:
        path = self._file("metrics", f"{metric}.py")
        spec = importlib.util.spec_from_file_location(
            f"bench_metric_{metric.replace('.', '_')}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod.read

    def metrics(self, cell: str, trace: bool) -> List[dict]:
        key = "per_layer" if trace else "end_to_end"
        return [m for m in self.spec[key]
                if cell in m.get("workloads", [cell])]


# ------------------------------------------------------------ the run
@dataclasses.dataclass
class SessionRecord:
    session: generator.Session
    arrival: float                    # wall clock (perf_counter)
    due: List[int]                    # tick each turn comes due
    job: object                       # the program's SessionJob
    submitted: float = 0.0            # wall clock the harness submitted
    tokens: List[float] = dataclasses.field(default_factory=list)
    admitted: Optional[float] = None  # wall clock the prefill started

    def turn_start(self, k: int) -> int:
        """Index of turn k's first token in the session's output."""
        return sum(n for _, n in self.session.turns[:k])


@dataclasses.dataclass
class Run:
    """What a metric reader sees."""
    cell: str
    cfg: dict
    mix: dict
    seconds: float
    t_open: float
    t_close: float
    sessions: List[SessionRecord]
    tick_walls: Dict[int, float]
    spans: List[tuple]                # (name, t0, t1)
    steps: List[tuple]                # (t0, t1, filled lengths decoding)
    setup_s: float
    device_kind: str
    trace: Optional[dict]             # reduced device trace
    # traced runs only: the program's spans lined up with the device
    # trace (bench/spans.py `reduce`), and the change of each of the
    # engine's counters over the window
    program_spans: Optional[dict] = None
    engine_counters: Optional[Dict[str, int]] = None

    def in_window(self, t: float) -> bool:
        return self.t_open <= t <= self.t_close

    def first_turn_waits(self, end: str) -> List[float]:
        """Seconds from each in-window arrival to its first token
        (`end="token"`) or to the start of its prefill
        (`end="admit"`); one still waiting at the close counts with
        its age then."""
        out = []
        for r in self.sessions:
            t = r.tokens[0] if end == "token" and r.tokens else \
                r.admitted if end == "admit" else None
            out.append(min(t, self.t_close) - r.arrival if t is not None
                       else self.t_close - r.arrival)
        return out

    def token_gaps(self) -> List[float]:
        """Gaps between consecutive tokens of one turn, for gaps that
        end in the window."""
        out = []
        for r in self.sessions:
            for k in range(len(r.session.turns)):
                lo = r.turn_start(k)
                hi = min(lo + r.session.turns[k][1], len(r.tokens))
                ts = r.tokens[lo:hi]
                out += [b - a for a, b in zip(ts, ts[1:])
                        if b <= self.t_close]
        return out

    def tokens_in_window(self) -> int:
        return sum(1 for r in self.sessions for t in r.tokens
                   if self.in_window(t))

    def span_seconds(self, name: str) -> List[float]:
        return [b - a for n, a, b in self.spans
                if n == name and self.in_window(b)]

    def queue_at(self, t: float) -> int:
        """Sessions arrived by wall time `t` whose prefill had not
        started: the admission queue."""
        return sum(1 for r in self.sessions if r.arrival <= t
                   and (r.admitted is None or r.admitted > t))


def decode_device(run: Run):
    """(filled lengths of each decode step in the window, device seconds
    of the programs run inside the engine's step calls), or None when
    the trace holds no such run or not one for each step."""
    if run.trace is None:
        return None
    got = run.trace["programs_by_host"].get("step",
                                             {"count": 0, "seconds": 0})
    steps = [lengths for t0, _, lengths in run.steps if t0 >= run.t_open]
    if not got["count"] or got["count"] != len(steps):
        print(f"decode program: {got['count']} runs in the trace, "
              f"{len(steps)} steps in the window", file=sys.stderr)
        return None
    return steps, got["seconds"]


def percentile(values, q: float) -> Optional[float]:
    return float(np.percentile(np.asarray(values, np.float64), q)) \
        if len(values) else None


# -------------------------------------------------------------- probe
class Probe:
    """Wraps the engine's admit, step, pause and resume on the instance:
    each call is timed on the wall clock (and, when tracing, wrapped in
    a profiler annotation), and the tokens it hands to the host are
    stamped when it returns. It also wraps the engine's prefill and
    decode programs to keep the logits rows they produce for the
    requests in `keep` (the decode step's rows come from the host copy
    that the engine itself made, so keeping them moves nothing more).
    Kept rows are copied into rows that `reserve` wrote before the
    window, so that the window maps no new memory for them."""

    def __init__(self, engine, annotate: bool):
        import jax
        self.engine = engine
        self.spans: List[tuple] = []
        self.steps: List[tuple] = []
        self.stamps: Dict[str, List[float]] = {}
        self.admits: Dict[str, float] = {}
        self.keep: set = set()
        self.rows: Dict[str, list] = {}
        self._logits = None
        self._pool = np.empty((0, 0))
        self._used = 0
        self._annotation = jax.profiler.TraceAnnotation if annotate \
            else None
        for name in ("_prefill", "_decode"):
            setattr(engine, name, self._capture(getattr(engine, name)))
        for name in ("admit", "step", "pause", "resume"):
            setattr(engine, name, self._wrap(name, getattr(engine, name)))

    def reserve(self, rows: int):
        """Rows for the kept logits, of the width and type of the last
        logits the programs produced, each written once now."""
        like = np.asarray(self._logits)
        self._pool = np.empty((rows, like.shape[-1]), like.dtype)
        self._pool.fill(0)
        self._used = 0

    def _kept_row(self, row) -> np.ndarray:
        """`row`, copied into the next reserved row where one is left."""
        if self._used == len(self._pool) or row.dtype != self._pool.dtype:
            return row.copy()
        out = self._pool[self._used]
        self._used += 1
        out[...] = row
        return out

    def region(self, name: str):
        if self._annotation is None:
            return contextlib.nullcontext()
        return self._annotation(f"bench.{name}")

    def _capture(self, fn):
        def call(*args, **kwargs):
            out = fn(*args, **kwargs)
            self._logits = out[1]
            return out
        return call

    def _wrap(self, name, fn):
        eng = self.engine

        def call(*args):
            if name == "step":
                act = eng.live & eng.active
                lengths = eng.lengths[act].tolist()
                reqs = [(s, eng.slot_req[s]) for s in np.flatnonzero(act)
                        if s in eng.slot_req]
            t0 = time.perf_counter()
            with self.region(name):
                out = fn(*args)
            t1 = time.perf_counter()
            self.spans.append((name, t0, t1))
            if name == "admit":
                rid = args[0].rid
                self.admits[rid] = t0
                self.stamps.setdefault(rid, []).append(t1)
                if rid in self.keep:
                    self.rows[rid] = [
                        self._kept_row(np.asarray(self._logits)[0])]
            elif name == "step" and lengths:
                self.steps.append((t0, t1, lengths))
                kept = [(s, r) for s, r in reqs if r.rid in self.keep]
                host = np.asarray(self._logits) if kept else None
                for s, req in kept:
                    self.rows[req.rid].append(self._kept_row(host[s]))
                for _, req in reqs:
                    got = self.stamps.setdefault(req.rid, [])
                    got += [t1] * (len(req.generated) - len(got))
            return out

        return call


class CompileCounter:
    """Programs compiled, or loaded from the persistent cache, while
    `counting` is set."""

    def __init__(self):
        import jax
        self.counting = False
        self.count = 0

        def on_duration(event, duration, **_):
            # fires for a fresh compile and for a persistent-cache load
            if self.counting and \
                    event == "/jax/core/compile/backend_compile_duration":
                self.count += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)


@functools.lru_cache(maxsize=None)
def compile_counter() -> CompileCounter:
    """The process's one counter (a listener stays registered)."""
    return CompileCounter()


def process_start() -> float:
    """Wall-clock (time.time) start of this process, from /proc."""
    try:
        ticks = int(pathlib.Path("/proc/self/stat").read_text()
                    .rsplit(")", 1)[1].split()[19])
        boot = next(float(line.split()[1]) for line in
                    pathlib.Path("/proc/stat").read_text().splitlines()
                    if line.startswith("btime"))
        return boot + ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError, StopIteration):
        return time.time()


def say(msg: str):
    print(msg, flush=True)


# -------------------------------------------------------------- set-up
def build(cfg: dict, seed: int, device):
    """Weights from the seed, and the scheduler over a fresh engine."""
    import jax.numpy as jnp
    from repro.parallel.sharding import single_device_rules
    from repro.platform import (HierarchySpec, HostDecl, Platform,
                                PolicyDecl)
    sv = cfg["serving"]
    params = reference.init_params(seed, cfg, jnp.dtype(sv["dtype"]),
                                   device)
    spec = HierarchySpec(
        hosts=(HostDecl(count=1),),
        policy=PolicyDecl.static(tau_hot=sv["tau_hot_s"],
                                 tau_be=sv["tau_be_s"], ema_alpha=1.0),
        step_time=sv["step_time_s"])
    sched = Platform.compile(spec).scheduler(
        arch.of(cfg).program_config(cfg), params,
        single_device_rules(device),
        max_slots=sv["slots"], max_len=sv["max_len"],
        compute_dtype=jnp.dtype(sv["dtype"]),
        pause_idle_steps=sv["pause_idle_steps"])
    return params, sched


def warm_up(sched, cfg: dict, mix: dict, rng):
    """Drive the scheduler through every program the cell's traffic
    uses: a prefill per prompt bucket, the splices and decode, and (for
    sessions of several turns) one pause and resume (the slot index is
    traced, so one round trip serves every slot)."""
    from repro.serving.scheduler import SessionJob, Turn
    sv = cfg["serving"]
    several = int(mix.get("turns", 1)) > 1
    jobs = []
    for i, b in enumerate(generator.prompt_buckets(mix, sv["max_len"])):
        turns = [Turn(sched.now, 2)]
        if several and i == 0:
            turns.append(Turn(sched.now + 2 + sv["pause_idle_steps"] + 8,
                              2))
        jobs.append(SessionJob(
            f"warm-{i}", rng.integers(1, cfg["vocab_size"], b)
            .astype(np.int32), turns))
    sched.submit_all(jobs)
    while sched.pending_work():
        sched.tick()


def kept_sessions(sessions, rng, seconds: float, tokens: int) -> List[str]:
    """Sessions whose logits the window keeps, drawn from the seed
    among those that arrive in the first 60% of the window (time to
    finish): the one with the longest context first, then others in the
    seed's order, until they hold twice `tokens` new tokens."""
    early = [s for s in sessions if s.t_arrival <= 0.6 * seconds]
    if not early:
        return []
    first = max(early, key=lambda s: (s.context, s.sid))
    rest = [s for s in early if s is not first]
    out, n = [], 0
    for s in [first] + [rest[i] for i in rng.permutation(len(rest))]:
        out.append(s.sid)
        n += s.new_tokens
        if n >= 2 * tokens:
            break
    return out


# -------------------------------------------------------------- window
def window(sched, sessions, seconds: float, step_time: float, probe):
    """Offer the sessions open-loop for `seconds`; returns the records,
    the wall time at which each tick started, and the window's ends."""
    from repro.serving.scheduler import SessionJob, Turn
    pending = deque(sessions)
    records: List[SessionRecord] = []
    tick_walls: Dict[int, float] = {}
    m = sched.metrics
    # no cyclic collection inside the window: a full pass over the
    # process's objects would stall the host for tens of milliseconds
    gc.collect()
    gc.freeze()
    gc.disable()
    t_open = time.perf_counter()
    t_close = t_open + seconds
    with probe.region("window"):
        while True:
            t = time.perf_counter()
            if t >= t_close:
                break
            while pending and t_open + pending[0].t_arrival <= t:
                s = pending.popleft()
                due, turns = [], []
                for gap, n in s.turns:
                    d = sched.now if not due else \
                        due[-1] + turns[-1].max_new + gap
                    due.append(d)
                    turns.append(Turn(d, n))
                job = SessionJob(s.sid, s.prompt, turns)
                records.append(SessionRecord(s, t_open + s.t_arrival, due,
                                             job, submitted=t))
                sched.submit(job)
            tick_walls[sched.now] = t
            steps = m["decode_steps"]
            with probe.region("tick"):
                sched.tick()
            if m["decode_steps"] == steps:
                wake = min(t + step_time, t_close,
                           t_open + pending[0].t_arrival if pending
                           else t_close)
                with probe.region("pace"):
                    time.sleep(max(0.0, wake - time.perf_counter()))
    gc.enable()
    gc.unfreeze()
    for r in records:
        r.tokens = probe.stamps.get(r.session.sid, [])
        r.admitted = probe.admits.get(r.session.sid)
    return records, tick_walls, t_open, t_close


# --------------------------------------------------------- correctness
def served_turns(r: SessionRecord) -> List[int]:
    """The session's output up to the end of its last finished turn."""
    got = len(r.job.request.generated) if r.job.request is not None else 0
    end = 0
    for _, n in r.session.turns:
        if end + n > got:
            break
        end += n
    return r.job.request.generated[:end] if end else []


def finished_sample(records, keep: List[str], rows: Dict[str, list],
                    tokens: int, max_requests: int):
    """The kept sessions that the window finished (a turn or more), in
    the order they were drawn, until `tokens` served tokens are in the
    sample: (record, served tokens, the program's logits rows)."""
    by_sid = {r.session.sid: r for r in records}
    out, n = [], 0
    for sid in keep:
        r = by_sid.get(sid)
        served = served_turns(r) if r is not None else []
        if not served:
            continue
        got = rows.get(sid, [])
        if len(got) < len(served):
            raise RuntimeError(f"{sid}: {len(got)} logits rows kept for "
                               f"{len(served)} served tokens")
        out.append((r, served, np.stack(got[:len(served)])))
        n += len(served)
        if n >= tokens or len(out) >= max_requests:
            break
    return out


def check(res: dict, controls=()) -> None:
    """Compare each sampled request with the float32 reference: the
    relative error of the program's logits, and the widest gap of a
    served token's reference logit below the reference's best. For each
    precision in `controls`, the same for the reference computed in it
    and put in the program's place. Frees the weights."""
    params, cfg = res.pop("params"), res["run"].cfg
    rows = []
    t0 = time.perf_counter()
    for r, served, prog in res.pop("sample"):
        got = reference.compare(params, cfg, r.session.prompt, served,
                                prog, controls)
        rows.append({"sid": r.session.sid, "prompt": len(r.session.prompt),
                     "served": len(served), **got["program"],
                     "control": {c: got[c] for c in controls}})
    res["checks"] = rows
    res["check_s"] = time.perf_counter() - t0
    del params
    gc.collect()


def as_control(res: dict, precision: str) -> dict:
    """The run's result with the control's numbers in the program's
    place."""
    return dict(res, checks=[dict(c, **c["control"][precision])
                             for c in res["checks"]])


# ----------------------------------------------------------------- run
def reduce_trace(directory: str) -> Tuple[Optional[dict], Optional[dict]]:
    """The newest trace the profiler wrote under `directory`, reduced
    (`xplane.reduce`), and the program's spans lined up with its device
    (`spans.reduce`); None for each without a device plane."""
    found = sorted(glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    reduced = xplane.reduce(found[-1]) if found else None
    if reduced is None:
        return None, None
    return reduced, spans.reduce(found[-1], reduced["offset_s"])


def measure(bench: Bench, workload: str, seed: int, seconds: float,
            trace: bool) -> dict:
    """Set-up and the measured window of one cell on the first device
    JAX has. The program's state is freed on return; the weights and
    the sample for the check stay in the result."""
    t_start = process_start()
    import jax
    from repro.obs.trace import enable_spans
    cell = bench.cell(workload)
    devices = jax.devices()
    dev = devices[0]
    cfg, mix = bench.config(cell["config"]), bench.traffic(cell["traffic"])
    limits = bench.limits(workload)
    sv = cfg["serving"]
    compiles = compile_counter()

    params, sched = build(cfg, seed, dev)
    probe = Probe(sched.engine, annotate=trace)
    rng = np.random.default_rng(seed)
    warm_up(sched, cfg, mix, rng)
    sessions = generator.schedule(mix, seed, seconds, cfg["vocab_size"],
                                  sv["max_len"])
    keep = kept_sessions(sessions, np.random.default_rng([seed, 1]),
                         seconds, limits["sample_tokens"])
    probe.keep = set(keep)
    probe.reserve(sum(s.new_tokens for s in sessions if s.sid in probe.keep))
    before = dict(sched.metrics)
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    engine_counters = dict(sched.engine.counters) if trace else None
    if trace:
        jax.profiler.start_trace(trace_dir)
    setup_s = time.time() - t_start
    compiles.count, compiles.counting = 0, True
    enable_spans(trace)
    try:
        records, tick_walls, t_open, t_close = window(
            sched, sessions, seconds, sv["step_time_s"], probe)
    finally:
        enable_spans(False)
    compiles.counting = False
    reduced = program_spans = None
    if trace:
        jax.profiler.stop_trace()
        engine_counters = {k: v - engine_counters.get(k, 0)
                           for k, v in sched.engine.counters.items()}
        reduced, program_spans = reduce_trace(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
    stats = dev.memory_stats() or {}
    res = dict(
        records=records,
        run=Run(workload, cfg, mix, seconds, t_open, t_close, records,
                tick_walls, probe.spans, probe.steps, setup_s,
                dev.device_kind, reduced, program_spans, engine_counters),
        counters={k: v - before.get(k, 0)
                  for k, v in sched.metrics.items()},
        compiles=compiles.count,
        memory_peak=stats.get("peak_bytes_in_use"),
        device=dev, n_devices=len(devices), limits=limits, params=params)
    res["sample"] = finished_sample(records, keep, probe.rows,
                                    limits["sample_tokens"],
                                    limits["sample_requests"])
    # the program's state goes before the reference runs
    del sched, probe
    gc.collect()
    return res


def run(root, workload: str, seed: int, seconds: float,
        trace: bool) -> dict:
    """One cell once: set-up, the window, and the check."""
    res = measure(Bench(root), workload, seed, seconds, trace)
    check(res)
    return res


def metrics_line(bench: Bench, res: dict, trace: bool) -> dict:
    out = {}
    for m in bench.metrics(res["run"].cell, trace):
        v = bench.reader(m["name"])(res["run"])
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


def _within(value, limit) -> bool:
    return value is not None and math.isfinite(value) and value <= limit


def result_line(bench: Bench, res: dict, trace: bool) -> dict:
    """The run's last line of standard output."""
    checks, limits = res["checks"], res["limits"]
    failed = sum(not all(_within(c[k], limits[k]["limit"]) for k in NUMBERS)
                 for c in checks)
    dev = res["device"]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": res["n_devices"],
              "memory_peak_bytes": res["memory_peak"]}
    line = {"correct": bool(checks) and failed == 0,
            "attempted": len(res["records"]),
            "failed": failed,
            "metrics": metrics_line(bench, res, trace),
            "device": device}
    tr = res["run"].trace
    if trace and tr is not None:
        device["busy_s"] = tr["busy_s"]
        device["window_s"] = tr["window_s"]
        line["breakdown"] = {"device_ops": tr["top_ops"],
                             "idle_gaps": tr["idle_by_host"]}
    worst = {}
    for k in NUMBERS:
        vals = [c[k] for c in checks]
        bad = [v for v in vals if not (v is not None and math.isfinite(v))]
        worst[k] = None if bad or not vals else max(vals)
    line["checks"] = {k: {"value": worst[k], "limit": limits[k]["limit"]}
                      for k in NUMBERS}
    return line


def report(bench: Bench, res: dict, trace: bool) -> dict:
    """Print the earlier lines and return the result line."""
    run_, counters = res["run"], res["counters"]
    late = [r.submitted - r.arrival for r in run_.sessions]
    say(f"[window] {run_.seconds:g} s, {len(run_.sessions)} sessions "
        f"arrived, {run_.tokens_in_window()} tokens; scheduler counts: "
        + ", ".join(f"{k} {counters[k]}" for k in
                    ("ticks", "decode_steps", "idle_ticks", "admissions",
                     "pauses", "resumes", "parks", "unparks")))
    say(f"[window] programs compiled or loaded inside the window: "
        f"{res['compiles']}; peak_bytes_in_use {res['memory_peak']}")
    say(f"[window] generator lateness (due arrival to submission), "
        f"p50/max s: {percentile(late, 50)} / "
        f"{max(late) if late else None}")
    marks = np.arange(run_.t_open, run_.t_close + 1e-9, run_.seconds / 5)
    say("[window] admission queue at each fifth of the window: "
        + " ".join(str(run_.queue_at(t)) for t in marks[1:]))
    if run_.program_spans is not None:
        say("[trace] device idle under each program span, s: "
            + spans.split_line(run_.program_spans["totals"]))
    for c in res["checks"]:
        say(f"[check] {c['sid']}: prompt {c['prompt']}, served "
            f"{c['served']}, logit_err {c['logit_err']:.6f}, "
            f"token_gap {c['token_gap']:.6f}")
    say(f"[check] {sum(c['served'] for c in res['checks'])} served tokens "
        f"of {len(res['checks'])} finished requests against the float32 "
        f"reference in {res['check_s']:.1f} s")
    line = result_line(bench, res, trace)
    for name, c in line["checks"].items():
        print(f"check {name} {c['value']} limit {c['limit']}",
              file=sys.stderr, flush=True)
    return line
