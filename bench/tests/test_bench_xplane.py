"""The reduction from a profiler trace to busy time, program time, top
operations and idle gaps by what the host was doing: on a small trace
recorded on a TPU v5e (bench/tests/record_trace.py) and on hand-made
intervals."""
import pathlib

import pytest

from bench import harness, xplane

DATA = pathlib.Path(__file__).resolve().parent / "data"


def test_labels_take_the_innermost_annotation():
    anns = [(0, 100, "tick"), (10, 40, "step"), (100, 120, "pace")]
    segs = xplane._labels(anns, 0, 130, "harness")
    assert segs == [(0, 10, "tick"), (10, 40, "step"), (40, 100, "tick"),
                    (100, 120, "pace"), (120, 130, "harness")]
    gaps = [(5, 15), (50, 60), (110, 125)]
    assert dict(xplane._overlap_by_label(gaps, segs)) == {
        "tick": 15, "step": 5, "pace": 10, "harness": 5}


def test_union_and_clip():
    assert xplane._union([(5, 8), (0, 2), (1, 3), (8, 9)]) == \
        [[0, 3], [5, 9]]
    assert xplane._clip([(0, 2), (5, 9)], 1, 6) == [(1, 2), (5, 6)]


def test_recorded_chip_trace():
    """Five runs of a program named `decode_step`, each followed by
    10 ms of host sleep inside `bench.tick` and 5 ms in `bench.pace`."""
    path = DATA / "small.xplane.pb"
    got = xplane.reduce(str(path))
    assert got is not None
    # every program run lies in its bench.step call once the device
    # clock is lined up with the host's (it was 1.04 ms off here)
    assert got["programs_by_host"]["step"]["count"] == 5
    assert set(got["programs_by_host"]) == {"step"}
    # a program run spans its ops and the short waits between them
    assert got["programs_by_host"]["step"]["seconds"] == pytest.approx(
        got["busy_s"], rel=0.01)
    assert abs(got["offset_s"]) < 0.01
    assert 0 < got["busy_s"] < got["window_s"]
    # the window holds 5 x (10 + 5) ms of host sleep at least
    assert got["window_s"] >= 0.075
    idle = dict(got["idle_by_host"])
    assert idle["tick"] == pytest.approx(0.050, rel=0.3)
    assert idle["pace"] == pytest.approx(0.025, rel=0.3)
    assert sum(idle.values()) == pytest.approx(
        got["window_s"] - got["busy_s"], rel=1e-6)
    assert got["top_ops"][0][0] == "step/%fusion"
    assert all(sec > 0 for _, sec in got["top_ops"])


def test_no_device_plane_reduces_to_nothing(tmp_path):
    assert harness.reduce_trace(str(tmp_path)) == (None, None)
