"""The traffic generator: the same seed gives the same schedule, every
length stays within its clips, no context exceeds the configuration's
max_len, and every seed offers the same work in another order."""
import json
import pathlib

import numpy as np
import pytest

from bench.tests import tiny
from bench.traffic import generator

BENCH = pathlib.Path(__file__).resolve().parents[1]
CELLS = json.loads((BENCH.parent / "BENCHMARK.json").read_text())



def cells():
    for w in CELLS["workloads"]:
        entry = next(c for c in CELLS["configs"] if c["name"] == w["config"])
        cfg = json.loads((BENCH.parent / entry["file"]).read_text())
        mix = json.loads((BENCH / "traffic" / f"{w['traffic']}.json")
                         .read_text())
        yield pytest.param(cfg, mix, id=f"{w['config']}.{w['traffic']}")
    # sessions of several turns, with gaps, as the tests' agent mix has
    yield pytest.param(tiny.CONFIG, tiny.MIXES["agent"], id="tiny.agent")


SEED = 2**33 + 17          # seeds above 32 bits are valid


@pytest.mark.parametrize("cfg,mix", cells())
def test_same_seed_same_schedule(cfg, mix):
    a = generator.schedule(mix, SEED, 45, cfg["vocab_size"],
                           cfg["serving"]["max_len"])
    b = generator.schedule(mix, SEED, 45, cfg["vocab_size"],
                           cfg["serving"]["max_len"])
    assert [(s.sid, s.t_arrival, s.turns) for s in a] == \
        [(s.sid, s.t_arrival, s.turns) for s in b]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    c = generator.schedule(mix, SEED + 1, 45, cfg["vocab_size"],
                           cfg["serving"]["max_len"])
    assert [s.turns for s in a] != [s.turns for s in c]


@pytest.mark.parametrize("cfg,mix", cells())
def test_lengths_within_clips_and_contexts_fit(cfg, mix):
    max_len = cfg["serving"]["max_len"]
    for seed in (0, 1, SEED):
        sessions = generator.schedule(mix, seed, 51, cfg["vocab_size"],
                                      max_len)
        assert sessions and all(0 <= s.t_arrival < 51 for s in sessions)
        p, o = mix["prompt_tokens"], mix["output_tokens"]
        for s in sessions:
            assert p["min"] <= len(s.prompt) <= p["max"]
            assert len(s.turns) == mix.get("turns", 1)
            for k, (gap, n) in enumerate(s.turns):
                assert o["min"] <= n <= o["max"]
                if k:
                    g = mix["gap_ticks"]
                    assert g["min"] <= gap <= g["max"]
            assert s.context < max_len
            assert s.prompt.min() >= 1 and s.prompt.max() < \
                cfg["vocab_size"]
        # the engine pads every prompt to a warmed bucket
        buckets = generator.prompt_buckets(mix, max_len)
        for s in sessions:
            b = 1 << max(0, len(s.prompt) - 1).bit_length()
            assert min(b, max_len - 1) in buckets


@pytest.mark.parametrize("cfg,mix", cells())
def test_seeds_offer_the_same_work_in_another_order(cfg, mix):
    max_len = cfg["serving"]["max_len"]
    work = []
    for seed in (3, 4, SEED):
        sessions = generator.schedule(mix, seed, 45, cfg["vocab_size"],
                                      max_len)
        assert len(sessions) == generator.n_sessions(mix, 45) == \
            round(mix["rate_per_s"] * 45)
        t = np.array([s.t_arrival for s in sessions])
        gaps = np.diff(np.append(t, 45.0))
        work.append((sorted(len(s.prompt) for s in sessions),
                     sorted(x for s in sessions for t in s.turns for x in t),
                     np.sort(gaps)))
    for other in work[1:]:
        assert other[0] == work[0][0] and other[1] == work[0][1]
        assert np.allclose(other[2], work[0][2])
    lens = generator.quantiles(mix["prompt_tokens"], 1000)
    d = mix["prompt_tokens"]
    if d["dist"] == "lognormal":
        assert np.median(lens) == pytest.approx(d["median"], rel=0.01)


def test_a_context_past_max_len_is_refused():
    mix = {"rate_per_s": 5.0, "turns": 1,
           "prompt_tokens": {"dist": "uniform", "min": 90, "max": 100},
           "output_tokens": {"dist": "uniform", "min": 20, "max": 20}}
    with pytest.raises(ValueError, match="does not fit"):
        generator.schedule(mix, 0, 2, 512, 100)


def test_unknown_distribution_is_refused():
    with pytest.raises(ValueError, match="unknown length distribution"):
        generator.quantiles({"dist": "pareto", "min": 1, "max": 2}, 4)
