"""Device-idle time under the program's spans (bench/spans.py) on
hand-made intervals: nested spans, a gap that straddles a span's end,
spans clipped to the window, and the numbers read from them; and on a
small trace recorded on a TPU v5e."""
import pathlib
import shutil

import pytest

from bench import harness, spans, xplane
from bench.tests import tiny

DATA = pathlib.Path(__file__).resolve().parent / "data"

# ns; one thread line "m" and another, "t"
EVENTS = [
    (8, 80, "scheduler.tick", "m"),
    (10, 60, "engine.step", "m"),
    (10, 15, "engine.launch", "m"),
    (15, 40, "engine.fetch", "m"),
    (40, 50, "engine.sample", "m"),
    (50, 60, "engine.retire", "m"),
    (62, 68, "engine.admit", "m"),
    (85, 120, "scheduler.tick", "m"),      # ends past the window
    (90, 110, "engine.step", "m"),
    (130, 140, "scheduler.tick", "m"),     # outside the window
    (20, 30, "other", "t"),
]
BUSY = [[0, 5], [12, 38], [55, 58], [70, 88], [95, 97]]
# idle gaps in [0, 100]: 5-12, 38-55, 58-70, 88-95, 97-100 (46 ns)


@pytest.fixture
def inst():
    return spans.idle_under(EVENTS, BUSY, 0, 100)


def _by(inst, name):
    return [r for r in inst if r["name"] == name]


def test_idle_and_self_idle_of_nested_spans(inst):
    got = {(r["name"], r["t0"]): (r["idle"] * 1e9, r["self_idle"] * 1e9)
           for r in inst}
    want = {("scheduler.tick", 8): (33, 6), ("engine.step", 10): (21, 0),
            ("engine.launch", 10): (2, 2), ("engine.fetch", 15): (2, 2),
            # the gap 38-55 straddles the fetch's end and the sample
            ("engine.sample", 40): (10, 10), ("engine.retire", 50): (7, 7),
            ("engine.admit", 62): (6, 6),
            # clipped to the window's end
            ("scheduler.tick", 85): (10, 2), ("engine.step", 90): (8, 8),
            ("other", 20): (0, 0)}
    assert got.keys() == want.keys()
    for k, (idle, own) in want.items():
        assert got[k] == (pytest.approx(idle), pytest.approx(own)), k


def test_parents_clipping_and_lines(inst):
    tick, step = _by(inst, "scheduler.tick")[0], _by(inst, "engine.step")
    assert all(inst[r["parent"]]["name"] == "engine.step"
               for r in inst if r["name"] in ("engine.launch",
                                              "engine.retire"))
    assert inst[step[0]["parent"]] is tick
    assert _by(inst, "other")[0]["parent"] is None
    assert step[1]["t1"] == 100 and len(_by(inst, "scheduler.tick")) == 2


def test_self_idle_adds_up_to_at_most_the_window_idle(inst):
    tot = spans.totals(inst)
    # 46 ns idle in the window, 3 of them (5-8) under no span
    assert sum(t["self_idle_s"] for t in tot.values()) * 1e9 == \
        pytest.approx(43)
    assert tot["engine.step"]["count"] == 2
    assert tot["engine.step"]["idle_s"] * 1e9 == pytest.approx(29)
    assert spans.split_line(tot).startswith("engine.sample ")


def test_step_and_tick_idle(inst):
    # steps 21 and 8 ns; the ticks' own idle 33 - 21 - 6 and 10 - 8
    assert spans.step_idle_ms(inst) == pytest.approx(14.5e-6)
    assert spans.tick_idle_ms(inst) == pytest.approx(4e-6)
    assert spans.step_idle_ms([]) is None and spans.tick_idle_ms([]) is None


def test_d2h_kb_per_token():
    assert spans.d2h_kb_per_token(2 * 1024 * 1024, 8) == 256
    assert spans.d2h_kb_per_token(None, 8) is None
    assert spans.d2h_kb_per_token(1024, 0) is None


def test_recorded_chip_trace_puts_idle_under_its_span():
    """Five ticks recorded on a TPU v5e (bench/tests/record_spans.py):
    the device runs only while `engine.launch` or `engine.fetch` is
    open, and each host sleep lands under the span that slept."""
    path = str(DATA / "spans.xplane.pb")
    red = xplane.reduce(path)
    got = spans.reduce(path, red["offset_s"])
    tot, inst = got["totals"], got["instances"]
    assert all(tot[n]["count"] == 5 for n in tot)
    assert red["programs_by_host"]["step"]["count"] == 5
    # the device ran only while the launch or the fetch was open
    busy = red["busy_s"]
    assert tot["engine.launch"]["idle_s"] + tot["engine.fetch"]["idle_s"] \
        + busy == pytest.approx(
            sum(r["t1"] - r["t0"] for r in inst
                if r["name"] in ("engine.launch", "engine.fetch")) * 1e-9,
            rel=0.01)
    # 4 ms in sample, 2 ms in retire, 3 ms in the tick after the step
    assert tot["engine.sample"]["self_idle_s"] == pytest.approx(0.020,
                                                                rel=0.3)
    assert tot["engine.retire"]["self_idle_s"] == pytest.approx(0.010,
                                                                rel=0.3)
    assert tot["scheduler.tick"]["self_idle_s"] == pytest.approx(0.015,
                                                                 rel=0.3)
    # the same gaps as the harness's own annotations see them
    idle = dict(red["idle_by_host"])
    assert tot["engine.step"]["idle_s"] == pytest.approx(idle["step"],
                                                         rel=0.01)
    assert tot["scheduler.tick"]["self_idle_s"] == pytest.approx(
        idle["tick"], rel=0.01)
    # what no span covers is the pace between ticks
    uncovered = got["window_idle_s"] - sum(t["self_idle_s"]
                                           for t in tot.values())
    assert uncovered == pytest.approx(idle["pace"] + idle["harness"],
                                      rel=0.01)


def test_the_harness_reduces_the_spans_of_its_trace(tmp_path):
    """`harness.reduce_trace` on a profiler directory that holds the
    recorded trace gives the reduction above, and its step idle."""
    d = tmp_path / "plugins" / "profile" / "run"
    d.mkdir(parents=True)
    shutil.copy(DATA / "spans.xplane.pb", d / "host.xplane.pb")
    reduced, got = harness.reduce_trace(str(tmp_path))
    path = str(DATA / "spans.xplane.pb")
    assert reduced == xplane.reduce(path)
    assert got == spans.reduce(path, reduced["offset_s"])
    tot = got["totals"]
    assert tot["engine.step"]["count"] == 5
    assert tot["engine.sample"]["self_idle_s"] == pytest.approx(0.020,
                                                                rel=0.3)
    # each step: the 6 ms of sleep in sample and retire, and the host's
    # share of the launch and the fetch
    assert 6 <= spans.step_idle_ms(got["instances"]) < 12
    # the tick's own: 3 ms after the step
    assert spans.tick_idle_ms(got["instances"]) == pytest.approx(3, rel=0.3)


@pytest.mark.parametrize("trace", [True, False], ids=["traced", "untraced"])
def test_only_a_traced_run_reads_the_programs_counters(tmp_path, trace):
    """A traced run puts the engine's transfer counter over the window
    on the result line (`d2h_kb_per_token`); an untraced one reads
    neither the counters nor the spans. On the CPU there is no device
    plane, so no span metric."""
    from repro.obs import trace as obs
    root = tiny.make_root(tmp_path)
    res = harness.run(root, "tiny.chat", 2**31 + 11, 1.0, trace)
    run = res["run"]
    assert not obs._SPANS
    assert run.program_spans is None
    line = harness.result_line(harness.Bench(root), res, trace)
    if trace:
        assert run.engine_counters["d2h_bytes"] > 0
        got = line["metrics"]["d2h_kb_per_token"]
        assert got["unit"] == "KiB/token"
        assert got["value"] == pytest.approx(
            run.engine_counters["d2h_bytes"] / run.tokens_in_window()
            / 1024)
        assert "step_idle_ms" not in line["metrics"]
    else:
        assert run.engine_counters is None
        assert "d2h_kb_per_token" not in line["metrics"]
