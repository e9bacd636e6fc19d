"""Substrate tests: checkpoint roundtrip/atomicity/tiering, data pipeline
determinism + resume, watchdog semantics, tiered store behavior, expert
store plans, serving engine generation + pause/resume."""
import json
import math
import pathlib
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint.manager import CheckpointConfig, CheckpointManager
from repro.core.policy import Tier, TieringPolicy
from repro.data.pipeline import DataConfig, PrefetchIterator, SyntheticLM
from repro.runtime.tiers import TierSpec, TieredStore
from repro.tiering.expert_store import ExpertStore
from repro.train.watchdog import RollbackSignal, Watchdog, WatchdogConfig


# ---------------------------------------------------------------------------
# checkpointing
# ---------------------------------------------------------------------------

def _tree(seed=0):
    k = jax.random.PRNGKey(seed)
    return {"a": jax.random.normal(k, (8, 16)),
            "b": {"c": jnp.arange(10, dtype=jnp.int32),
                  "d": jnp.ones((3,), jnp.bfloat16)}}


def test_checkpoint_roundtrip(tmp_path):
    mgr = CheckpointManager(CheckpointConfig(root=str(tmp_path)))
    tree = _tree()
    mgr.save(10, tree, extra={"data_step": 10})
    out, extra = mgr.restore(jax.tree.map(jnp.zeros_like, tree))
    assert extra["data_step"] == 10
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(out)):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


def test_checkpoint_corruption_detected(tmp_path):
    mgr = CheckpointManager(CheckpointConfig(root=str(tmp_path)))
    tree = _tree()
    path = mgr.save(1, tree)
    manifest = json.loads((path / "manifest.json").read_text())
    victim = list(manifest["leaves"].values())[0]["file"]
    arr = np.load(path / victim)
    arr.ravel()[0] += 1 if arr.dtype.kind in "iu" else 1.0
    np.save(path / victim, arr)
    with pytest.raises(IOError, match="checksum"):
        mgr.restore(tree)


def test_checkpoint_gc_and_tier_demotion(tmp_path):
    mgr = CheckpointManager(CheckpointConfig(
        root=str(tmp_path), keep=3, fast_tier_keep=1))
    tree = _tree()
    for s in (1, 2, 3, 4, 5):
        mgr.save(s, tree)
    assert mgr.latest_step() == 5
    assert mgr.tier_of(5) == "dram"          # newest on fast tier
    assert mgr.tier_of(4) == "flash"         # demoted
    assert mgr.tier_of(1) is None            # GC'd
    out, _ = mgr.restore(tree, step=3)       # restore from flash works
    assert out is not None


def test_checkpoint_partial_write_invisible(tmp_path):
    """A .tmp dir (simulated crash mid-save) must not be restorable."""
    mgr = CheckpointManager(CheckpointConfig(root=str(tmp_path)))
    tree = _tree()
    mgr.save(1, tree)
    crash = tmp_path / "dram" / "step_00000002.tmp"
    crash.mkdir()
    (crash / "manifest.json").write_text("{}")
    assert mgr.latest_step() == 1


def test_elastic_restore_to_different_sharding(tmp_path):
    """Save unsharded, restore with explicit shardings (1-device mesh) —
    the multi-device re-mesh path is exercised in test_distributed.py."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((1,), ("x",))
    mgr = CheckpointManager(CheckpointConfig(root=str(tmp_path)))
    tree = _tree()
    mgr.save(1, tree)
    sh = jax.tree.map(lambda a: NamedSharding(
        mesh, P(*( ("x",) + (None,) * (a.ndim - 1)))), tree)
    out, _ = mgr.restore(tree, shardings=sh)
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(out)):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


# ---------------------------------------------------------------------------
# data pipeline
# ---------------------------------------------------------------------------

def test_data_determinism_and_host_sharding():
    cfg1 = DataConfig(vocab=97, seq_len=16, global_batch=8)
    ds = SyntheticLM(cfg1)
    b1, b2 = ds.batch_at(3), ds.batch_at(3)
    np.testing.assert_array_equal(b1["tokens"], b2["tokens"])
    assert not np.array_equal(ds.batch_at(4)["tokens"], b1["tokens"])
    # host sharding partitions the batch
    h0 = SyntheticLM(DataConfig(vocab=97, seq_len=16, global_batch=8,
                                n_hosts=2, host_id=0))
    h1 = SyntheticLM(DataConfig(vocab=97, seq_len=16, global_batch=8,
                                n_hosts=2, host_id=1))
    assert h0.batch_at(0)["tokens"].shape == (4, 16)
    assert not np.array_equal(h0.batch_at(0)["tokens"],
                              h1.batch_at(0)["tokens"])


def test_prefetch_resume():
    ds = SyntheticLM(DataConfig(vocab=97, seq_len=8, global_batch=2))
    it = PrefetchIterator(ds, start_step=0)
    first = next(it)
    state = it.state()
    it.close()
    it2 = PrefetchIterator(ds, start_step=state["step"])
    second = next(it2)
    it2.close()
    np.testing.assert_array_equal(second["tokens"], ds.batch_at(1)["tokens"])
    np.testing.assert_array_equal(first["tokens"], ds.batch_at(0)["tokens"])


# ---------------------------------------------------------------------------
# watchdog
# ---------------------------------------------------------------------------

def test_watchdog_nan_rollback():
    wd = Watchdog()
    wd.begin_step()
    with pytest.raises(RollbackSignal):
        wd.end_step(1, float("nan"))


def test_watchdog_spike_rollback():
    wd = Watchdog(WatchdogConfig(max_loss_spike=2.0))
    for i in range(10):
        wd.begin_step()
        wd.end_step(i, 1.0)
    wd.begin_step()
    with pytest.raises(RollbackSignal):
        wd.end_step(11, 5.0)


def test_watchdog_straggler_detection():
    wd = Watchdog(WatchdogConfig(straggler_factor=5.0))
    for i in range(5):
        wd.begin_step()
        wd._t_last -= 0.01            # simulate 10ms steps
        wd.end_step(i, 1.0)
    wd.begin_step()
    wd._t_last -= 1.0                 # simulated 1s straggler
    ev = wd.end_step(6, 1.0)
    assert "straggler" in ev


# ---------------------------------------------------------------------------
# tiered store + policy
# ---------------------------------------------------------------------------

def _clocked_store(tau_hot=1.0, tau_be=10.0, dram_cap=10 * 2**20):
    clock = {"t": 0.0}
    pol = TieringPolicy(tau_hot=tau_hot, tau_be=tau_be, hysteresis=0.0,
                        ema_alpha=1.0)
    store = TieredStore(pol, specs={
        Tier.HBM: TierSpec(2**20, 819e9, 1e-7),
        Tier.DRAM: TierSpec(dram_cap, 45e9, 5e-7),
        Tier.FLASH: TierSpec(2**40, 7e9, 2e-5),
    }, clock=lambda: clock["t"])
    return store, clock


def test_tiered_store_promotes_hot_objects():
    store, clock = _clocked_store()
    x = np.ones(1024, np.float32)
    store.put("hot", x)
    for _ in range(6):
        clock["t"] += 0.1             # reuse interval 0.1s < tau_hot
        store.get("hot")
    assert store.tier_of("hot") == Tier.HBM


def test_tiered_store_demotes_cold_objects():
    store, clock = _clocked_store()
    store.put("cold", np.ones(1024, np.float32))
    for _ in range(4):
        clock["t"] += 100.0           # reuse interval >> tau_be
        store.get("cold")
    assert store.tier_of("cold") == Tier.FLASH


def test_tiered_store_capacity_pressure_demotes():
    store, clock = _clocked_store(dram_cap=8 * 4096)
    for i in range(8):
        clock["t"] += 0.01
        store.put(f"k{i}", np.ones(1024, np.float32))   # 4KiB each
    # DRAM full: next put must displace something to flash
    store.put("k8", np.ones(1024, np.float32))
    used = store.used_bytes(Tier.DRAM)
    assert used <= 8 * 4096
    assert store.used_bytes(Tier.FLASH) > 0


def test_policy_hysteresis_prevents_thrash():
    pol = TieringPolicy(tau_hot=1.0, tau_be=10.0, hysteresis=0.5,
                        ema_alpha=1.0)
    t = 0.0
    pol.observe("x", now=t)
    # interval 11s: above tau_be but inside the hysteresis band (10*1.5)
    t += 11.0
    assert pol.observe("x", now=t) == Tier.DRAM
    # interval 30s: beyond the band -> demote
    t += 30.0
    assert pol.observe("x", now=t) == Tier.FLASH


# ---------------------------------------------------------------------------
# expert store
# ---------------------------------------------------------------------------

def test_expert_store_residency_plan():
    pol = TieringPolicy(tau_hot=0.05, tau_be=5.0)
    es = ExpertStore(n_layers=2, n_experts=8, policy=pol)
    rng = np.random.default_rng(0)
    # expert 0 is hot (picked every step), expert 7 never picked
    for step in range(50):
        ids = np.concatenate([np.zeros(64, np.int64),
                              rng.integers(1, 7, 16)])
        es.observe_step({0: ids, 1: ids}, now=step * 0.01, tokens=80)
    plan = es.residency_plan(step_time=0.01)
    tiers = plan["tiers"]
    assert tiers[0, 0] == Tier.HBM            # always-selected expert
    assert tiers[0, 7] == Tier.FLASH          # never-selected expert
    assert plan["hbm_experts"] >= 2
    assert plan["flash_experts"] >= 2
