"""The harness finds a configuration, a traffic mix and a per-layer
metric added as new files, by the names in BENCHMARK.json, with no edit
to any file it already has."""
import json
import os
import pathlib
import shutil
import subprocess
import sys

import jax

from bench import harness
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
