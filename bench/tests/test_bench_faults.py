"""A run whose timed path is broken underneath comes out not correct:
the harness is driven without its look for a chip, at a reduced size,
once for each fault a served cell can have."""
import jax
import jax.numpy as jnp
import pytest

from bench import harness
from bench.tests import tiny

SEED = 2**32 + 5


def run(tmp_path, cell, fault=None, monkeypatch=None, controls=()):
    """One run of a tiny cell, with `fault` planted in the engine that
    the window drives; (result line, result)."""
    root = tiny.make_root(tmp_path)
    if fault is not None:
        real = harness.build

        def build(*args):
            params, sched = real(*args)
            fault(sched.engine)
            return params, sched
        monkeypatch.setattr(harness, "build", build)
    bench = harness.Bench(root)
    res = harness.measure(bench, cell, SEED, 2.0, False)
    harness.check(res, controls)
    return harness.result_line(bench, res, False), res


def state_unchanged(eng):
    """The decode step hands back the cache it was given: a copy taken
    before the call, since the step donates the cache it is given."""
    real = eng._decode

    def decode(params, *, token, cache, index):
        kept = jax.tree.map(jnp.copy, cache)
        return kept, real(params, token=token, cache=cache,
                          index=index)[1]
    eng._decode = decode


def half_batch(eng):
    """The first half of the slot grid is left out: its slots get the
    second half's logits."""
    real = eng._decode

    def decode(params, *, token, cache, index):
        new, logits = real(params, token=token, cache=cache, index=index)
        h = logits.shape[0] // 2
        return new, logits.at[:h].set(logits[h:])
    eng._decode = decode


def token_altered(eng):
    """Every seventh token a request receives is one id off."""
    real = eng.step

    def step():
        real()
        for req in eng.slot_req.values():
            if len(req.generated) % 7 == 0:
                req.generated[-1] = (req.generated[-1] + 1) % \
                    eng.cfg.vocab
    eng.step = step


def restore_unchanged(eng):
    """A resume leaves the cache as it was: the slot keeps stale KV."""
    real = eng.resume

    def resume(rid):
        before = eng.cache
        slot = real(rid)
        eng.cache = before
        return slot
    eng.resume = resume


@pytest.mark.parametrize("cell", ["tiny.chat", "tiny.agent"])
def test_a_sound_run_is_correct(tmp_path, cell):
    line, res = run(tmp_path, cell)
    assert line["correct"] is True
    assert line["failed"] == 0
    for name, c in line["checks"].items():
        assert 0 <= c["value"] <= c["limit"], name
    assert sum(c["served"] for c in res["checks"]) >= 40
    if cell == "tiny.agent":
        assert res["counters"]["pauses"] >= 1
        assert res["counters"]["resumes"] >= 1


@pytest.mark.parametrize("cell,fault", [
    ("tiny.chat", state_unchanged), ("tiny.chat", half_batch),
    ("tiny.chat", token_altered), ("tiny.agent", restore_unchanged)],
    ids=["state_unchanged", "half_batch", "token_altered",
         "restore_unchanged"])
def test_a_broken_timed_path_is_not_correct(tmp_path, monkeypatch, cell,
                                            fault):
    line, _ = run(tmp_path, cell, fault, monkeypatch)
    assert line["correct"] is False
    assert line["failed"] >= 1
    assert any(c["value"] is None or c["value"] > c["limit"]
               for c in line["checks"].values())
