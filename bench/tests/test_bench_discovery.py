"""The harness finds a configuration, an architecture, a traffic mix and
a per-layer metric added as new files, by the names in BENCHMARK.json
and the configuration's "arch", with no edit to any file it already
has."""
import json
import os
import pathlib
import shutil
import subprocess
import sys

import jax
import pytest

from bench import arch, counts, harness
from bench.tests import tiny

BENCH = pathlib.Path(__file__).resolve().parents[1]

NEW_METRIC = '''"""Ticks the scheduler ran per second of the window."""


def read(run):
    return len(run.tick_walls) / run.seconds
'''


def test_new_files_are_found_by_name(tmp_path):
    root = tiny.make_root(tmp_path)
    b = root / "bench"
    cfg = dict(tiny.CONFIG, name="tiny-deep", num_hidden_layers=3)
    (b / "configs" / "tiny-deep.json").write_text(json.dumps(cfg))
    mix = dict(tiny.MIXES["chat"], rate_per_s=20.0)
    (b / "traffic" / "tiny_busy.json").write_text(json.dumps(mix))
    (b / "limits" / "tiny-deep.busy.json").write_text(
        json.dumps(tiny.LIMITS))
    (b / "metrics" / "ticks_per_s.py").write_text(NEW_METRIC)
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "tiny-deep", "source": "test",
                            "file": "bench/configs/tiny-deep.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "tiny-deep.busy",
                              "config": "tiny-deep",
                              "traffic": "tiny_busy", "chips": 1,
                              "why": "test"})
    spec["per_layer"].append({"name": "ticks_per_s", "unit": "1/s",
                              "better": "higher", "source": "host_clock",
                              "layer": "scheduler", "moves": "tokens_per_s",
                              "workloads": ["tiny-deep.busy"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))

    bench = harness.Bench(root)
    res = harness.run(root, "tiny-deep.busy", 3, 1.0, True)
    assert res["run"].cfg["num_hidden_layers"] == 3
    assert res["run"].mix["rate_per_s"] == 20.0
    line = harness.result_line(bench, res, True)
    assert line["metrics"]["ticks_per_s"]["value"] > 0
    assert line["metrics"]["ticks_per_s"]["unit"] == "1/s"
    # a metric whose `workloads` do not list the cell is not reported
    assert set(line["metrics"]) == {"ticks_per_s"}
    assert line["correct"] is True
    assert line["device"]["platform"] == jax.devices()[0].platform


# a new architecture: the dense one, whose work counts carry a marker
NEW_ARCH = (BENCH / "arch" / "dense.py").read_text() + '''

_dense_step = decode_step


def decode_step(cfg, lengths):
    return dict(_dense_step(cfg, lengths), counted_by="tiny_new")
'''


def test_a_new_architecture_is_a_new_file(tmp_path):
    """A configuration whose "arch" names a module written as a new
    file under the root's bench/arch runs through `harness.run` and is
    correct; its work counts come from that module."""
    root = tiny.make_root(tmp_path)
    b = root / "bench"
    (b / "arch" / "tiny_new.py").write_text(NEW_ARCH)
    cfg = dict(tiny.CONFIG, name="tiny-new", arch="tiny_new")
    (b / "configs" / "tiny-new.json").write_text(json.dumps(cfg))
    (b / "limits" / "tiny-new.chat.json").write_text(
        json.dumps(tiny.LIMITS))
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "tiny-new", "source": "test",
                            "file": "bench/configs/tiny-new.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "tiny-new.chat", "config": "tiny-new",
                              "traffic": "tiny_chat", "chips": 1,
                              "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))

    bench = harness.Bench(root)
    res = harness.run(root, "tiny-new.chat", 4, 1.0, False)
    run = res["run"]
    assert arch.of(run.cfg).__file__ == str(b / "arch" / "tiny_new.py")
    assert counts.decode_step(run.cfg, [5, 9])["counted_by"] == "tiny_new"
    assert harness.result_line(bench, res, False)["correct"] is True


@pytest.mark.parametrize("name", [None, "no_such_arch"],
                         ids=["missing", "unknown"])
def test_an_unknown_architecture_is_an_error(tmp_path, name):
    cfg = {k: v for k, v in tiny.CONFIG.items() if k != "arch"}
    if name is not None:
        cfg["arch"] = name
    with pytest.raises(KeyError, match=r"known: \[.*'dense'.*\]"):
        arch.of(cfg)
    root = tiny.make_root(tmp_path)
    (root / "bench" / "configs" / "tiny.json").write_text(json.dumps(cfg))
    with pytest.raises(KeyError, match=r"known: \[.*'dense'.*\]"):
        harness.Bench(root).config("tiny")


def test_without_a_chip_the_benchmark_refuses(tmp_path):
    """In a directory that holds only BENCHMARK.json and bench/, on a
    machine where JAX finds no TPU, run.py exits non-zero and prints no
    result."""
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(BENCH.parent / "BENCHMARK.json", tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    cell = json.loads((BENCH.parent / "BENCHMARK.json").read_text())[
        "workloads"][0]["name"]
    got = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", cell, "--seed",
         str(2**31 + 7), "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=120)
    assert got.returncode != 0
    assert got.stdout == ""
    assert "TPU" in got.stderr
