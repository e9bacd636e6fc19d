"""Wall-clock spans on the served path and the engine's transfer counter:

  * with spans off, `span()` never reaches the profiler;
  * with spans on, under a profiler trace, the host plane holds
    `repro.scheduler.tick` > `repro.engine.step` >
    `repro.engine.{launch,fetch,sample,retire}` (and the pause and
    resume phases), one `engine.step` per counted decode step;
  * `d2h_bytes` counts exactly the logits and KV blocks the engine
    copied to the host;
  * greedy tokens are the same with spans on and off;
  * the engine's programs carry their names.
"""
import glob
import os

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.policy import TieringPolicy
from repro.models import model as M
from repro.obs import trace as obs_trace
from repro.parallel.sharding import single_device_rules
from repro.serving.engine import DecodeEngine
from repro.serving.scheduler import ContinuousScheduler, SessionJob, Turn

STEP_PHASES = ("launch", "fetch", "sample", "retire")


@pytest.fixture(scope="module")
def setup():
    cfg = get_config("gemma-2b", reduced=True)
    rules = single_device_rules()
    params, _ = M.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, rules, params


@pytest.fixture(autouse=True)
def spans_off_after():
    yield
    obs_trace.enable_spans(False)


def _scheduler(setup, pause_idle_steps=0):
    cfg, rules, params = setup
    eng = DecodeEngine(cfg, params, rules, max_slots=2, max_len=64,
                       policy=TieringPolicy(tau_hot=1e-12, tau_be=1e-9,
                                            ema_alpha=1.0),
                       step_time=2e-3)
    return ContinuousScheduler(eng, pause_idle_steps=pause_idle_steps)


def _jobs(cfg, turns):
    rng = np.random.default_rng(7)
    return [SessionJob(f"s{i}", rng.integers(1, cfg.vocab, 5 + 3 * i)
                       .astype(np.int32), list(t))
            for i, t in enumerate(turns)]


# one single-turn session, one that pauses through the store between
# its turns (pause_idle_steps=0), one that waits for a free slot
TURNS = [[Turn(0, 4)], [Turn(0, 3), Turn(6, 3)], [Turn(1, 2)]]


def _serve(setup, turns=TURNS):
    sched = _scheduler(setup)
    jobs = _jobs(setup[0], turns)
    sched.submit_all(jobs)
    while sched.pending_work():
        sched.tick()
    return sched, {j.sid: list(j.request.generated) for j in jobs}


def _host_events(directory):
    """(start, end, name) of the `repro.*` events in the trace."""
    path, = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                      recursive=True)
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out += [(e.start_ns, e.start_ns + e.duration_ns,
                         e.name[len("repro."):]) for e in line.events
                        if e.name.startswith("repro.")]
    return out


def _inside(outer, inner):
    return outer[0] <= inner[0] and inner[1] <= outer[1]


def _parent(events, ev, name):
    got = [o for o in events if o[2] == name and _inside(o, ev)]
    assert len(got) == 1, (ev, name)
    return got[0]


def test_spans_off_never_reach_the_profiler(setup, monkeypatch):
    def refuse(*a, **k):
        raise AssertionError("a span reached the profiler")

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", refuse)
    obs_trace.enable_spans(False)
    sched, tokens = _serve(setup)
    assert sched.metrics["pauses"] == 1 and sched.metrics["resumes"] == 1
    assert all(tokens.values())
    obs_trace.enable_spans(True)
    with pytest.raises(AssertionError, match="reached the profiler"):
        with obs_trace.span("engine.step"):
            pass


def test_spans_nest_on_the_host_plane(setup, tmp_path):
    obs_trace.enable_spans(True)
    with jax.profiler.trace(str(tmp_path)):
        sched, _ = _serve(setup)
    obs_trace.enable_spans(False)
    ev = _host_events(str(tmp_path))
    names = [e[2] for e in ev]
    steps = [e for e in ev if e[2] == "engine.step"]
    assert len(steps) == sched.metrics["decode_steps"]
    assert names.count("scheduler.tick") == sched.metrics["ticks"]
    for st in steps:
        _parent(ev, st, "scheduler.decode")
        _parent(ev, st, "scheduler.tick")
    for phase in STEP_PHASES:
        got = [e for e in ev if e[2] == f"engine.{phase}"]
        assert len(got) == len(steps), phase
        for e in got:
            _parent(ev, e, "engine.step")
    for outer, inner in (("engine.admit", ("prefill", "splice",
                                           "first_token")),
                         ("engine.pause", ("extract", "put")),
                         ("engine.resume", ("wait", "restore"))):
        assert names.count(outer) == {"engine.admit": 3, "engine.pause": 1,
                                      "engine.resume": 1}[outer]
        for name in inner:
            got = [e for e in ev if e[2] == f"engine.{name}"]
            assert len(got) == names.count(outer), name
            for e in got:
                _parent(ev, e, outer)
    for e in ev:
        if e[2] in ("engine.admit", "engine.resume"):
            _parent(ev, e, "scheduler.admission")
    for step in ("arrivals", "prefetch", "admission", "decode"):
        assert names.count(f"scheduler.{step}") == sched.metrics["ticks"]
    assert names.count("scheduler.boundaries") == \
        sched.metrics["decode_steps"]


@pytest.mark.parametrize("turns", [
    [[Turn(0, 4)], [Turn(0, 6)], [Turn(2, 3)]],
    TURNS,
], ids=["single-turn", "paused"])
def test_d2h_bytes_counts_what_reaches_the_host(setup, turns):
    sched, _ = _serve(setup, turns)
    eng = sched.engine
    cfg = setup[0]
    itemsize = np.dtype(eng.dtype).itemsize
    step_logits = eng.max_slots * cfg.vocab * itemsize
    first_logits = cfg.vocab * itemsize
    block = sum(a.nbytes for a in jax.tree.leaves(eng.cache)) \
        // eng.max_slots
    m = sched.metrics
    assert eng.counters["d2h_bytes"] == (
        step_logits * m["decode_steps"] + first_logits * m["admissions"]
        + block * m["pauses"])


def test_greedy_tokens_identical_with_spans_on_and_off(setup, tmp_path):
    _, off = _serve(setup)
    obs_trace.enable_spans(True)
    with jax.profiler.trace(str(tmp_path)):
        _, on = _serve(setup)
    obs_trace.enable_spans(False)
    assert on == off


def test_engine_programs_carry_their_names(setup):
    sched = _scheduler(setup)
    eng = sched.engine
    names = {f: getattr(eng, f).__name__
             for f in ("_zero_cache", "_prefill", "_decode")}
    assert names == {"_zero_cache": "init_cache", "_prefill": "prefill",
                     "_decode": "decode_step"}
    lowered = eng._decode.lower(
        eng.params, token=jax.numpy.zeros((eng.max_slots, 1), np.int32),
        cache=eng.cache, index=jax.numpy.zeros(eng.max_slots, np.int32))
    text = lowered.as_text(debug_info=True)
    assert "module @jit_decode_step" in text
    for scope in ("embed", "layers", "attention", "ffn", "head",
                  "kv_update"):
        assert f"{scope}/" in text, scope
