"""The benchmark's float32 reference at a reduced size: its weights have
the program's layout, it agrees with the program's own
`reference_logits`, and with what the engine serves through prefill,
decode and pause -> tiered store -> resume."""
import copy
import hashlib
import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import arch, harness, reference
from bench.tests import tiny

BENCH = pathlib.Path(__file__).resolve().parents[1]


def f32_config():
    cfg = copy.deepcopy(tiny.CONFIG)
    cfg["serving"]["dtype"] = "float32"
    return cfg


@pytest.mark.parametrize("name", ["mistral-nemo-12b", "deepseek-7b",
                                  "tiny"])
def test_weights_have_the_programs_layout(name):
    from repro.models import model as M
    cfg = {"tiny": tiny.CONFIG, "deepseek-7b": tiny.MHA}.get(name) or \
        json.loads((BENCH / "configs" / f"{name}.json").read_text())
    prog = jax.eval_shape(
        lambda k: M.init_params(k, arch.of(cfg).program_config(cfg))[0],
        jax.random.PRNGKey(0))
    ours = arch.of(cfg).param_shapes(cfg)
    is_shape = (lambda s: isinstance(s, tuple)
                and all(isinstance(e, int) for e in s))
    assert jax.tree.structure(ours, is_leaf=is_shape) == \
        jax.tree.structure(prog)
    assert jax.tree.leaves(ours, is_leaf=is_shape) == \
        [a.shape for a in jax.tree.leaves(prog)]


def test_agrees_with_the_programs_reference_logits():
    from repro.models import model as M
    from repro.parallel.sharding import single_device_rules
    cfg = f32_config()
    params = reference.init_params(5, cfg, jnp.float32)
    tokens = np.random.default_rng(0).integers(1, cfg["vocab_size"], 37)
    with jax.default_matmul_precision("highest"):
        theirs = np.asarray(M.reference_logits(
            params, arch.of(cfg).program_config(cfg),
            single_device_rules(jax.devices()[0]),
            jnp.asarray(tokens[None], jnp.int32))[0])
    # the rows that chose tokens 10..36, given tokens 0..35
    got = reference.compare(params, cfg, tokens[:10], tokens[10:],
                            theirs[9:36])["program"]
    assert got["logit_err"] < 1e-5


def test_agrees_with_the_engine_through_pause_and_resume():
    """float32 serving: the logits the engine produced, and every
    served token, agree with the reference to rounding after prefill,
    decode and three pause/resume round trips."""
    from repro.serving.scheduler import SessionJob, Turn
    cfg = f32_config()
    params, sched = harness.build(cfg, 7, jax.devices()[0])
    probe = harness.Probe(sched.engine, annotate=False)
    rng = np.random.default_rng(1)
    jobs = [SessionJob(f"s{i}", rng.integers(1, cfg["vocab_size"], n)
                       .astype(np.int32),
                       [Turn(0, 5), Turn(20, 4), Turn(40, 4), Turn(60, 3)])
            for i, n in enumerate((9, 30, 17))]
    probe.keep = {job.sid for job in jobs}
    rep = sched.run(jobs)
    assert rep["pauses"] >= 9 and rep["resumes"] >= 9
    for job in jobs:
        served = job.request.generated
        assert len(served) == 16 == len(probe.rows[job.sid])
        got = reference.compare(params, cfg, job.prompt, served,
                                np.stack(probe.rows[job.sid]))["program"]
        assert got["logit_err"] < 1e-4, job.sid
        assert got["token_gap"] < 1e-4, job.sid


def test_kept_rows_land_in_the_rows_reserved_before_the_window():
    """The probe copies a kept row into the next reserved row, equal to
    the row it was given, and copies it anew once the reserve is spent
    or where the row's type differs."""
    import types
    calls = {n: (lambda *a, **k: None) for n in
             ("_prefill", "_decode", "admit", "step", "pause", "resume")}
    probe = harness.Probe(types.SimpleNamespace(**calls), annotate=False)
    rows = np.arange(8 * 16, dtype=np.float32).reshape(8, 16)
    probe._logits = rows.astype(jnp.bfloat16)
    probe.reserve(3)
    assert probe._pool.shape == (3, 16)
    assert probe._pool.dtype == jnp.bfloat16
    wide = probe._kept_row(rows[5])
    assert wide.dtype == np.float32 and \
        not np.shares_memory(wide, probe._pool)
    np.testing.assert_array_equal(wide, rows[5])
    got = [probe._kept_row(r) for r in probe._logits[:4]]
    assert [np.shares_memory(g, probe._pool) for g in got] == \
        [True, True, True, False]
    np.testing.assert_array_equal(np.stack(got), probe._logits[:4])


def test_lower_precision_moves_the_logits():
    cfg = f32_config()
    params = reference.init_params(5, cfg, jnp.float32)
    tokens = np.random.default_rng(2).integers(1, cfg["vocab_size"], 20)
    exact = np.asarray(arch.of(cfg).hidden(params, cfg, tokens))[:20]
    for precision in ("int8", "fp8"):
        low = np.asarray(arch.of(cfg).hidden(params, cfg, tokens,
                                          precision))[:20]
        err = np.linalg.norm(low - exact) / np.linalg.norm(exact)
        assert 1e-4 < err < 0.2, precision


# sha256 of tiny's bf16 weights at seed 5 (each leaf's path, dtype, shape
# and bytes, in the tree's order), as the benchmark drew them when the
# parameter layout moved to bench/arch: the same leaves, in the same
# order, with the same draws, so a checkout's compiled set-up and the
# programs' inputs stay as they were
TINY_DIGEST = \
    "d573e0e260ef6bcd4dc8ee4b35c5fea6b83eda75017dc660dc8e5560e7c1783a"


def test_tiny_weights_are_pinned():
    h = hashlib.sha256()
    params = reference.init_params(5, tiny.CONFIG)
    for path, a in jax.tree_util.tree_flatten_with_path(params)[0]:
        a = np.asarray(a)
        h.update(jax.tree_util.keystr(path).encode())
        h.update(str(a.dtype).encode() + str(a.shape).encode())
        h.update(a.tobytes())
    assert h.hexdigest() == TINY_DIGEST
