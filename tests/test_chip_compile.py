"""Compiles the main path for one TPU v5e chip that is described, not
attached: the case-study kernels at the smoke widths and the served
decode step of mistral-nemo-12b at published widths (8 of 40 layers)
and of qwen3-moe-235b-a22b's served share (12 layers, 8 of 128
experts); and the decode step on the described 2x2 mesh under the serve
rules.

Nothing runs; the TPU compiler refuses here what it would refuse on the
chip (block tiling, scoped VMEM, dtypes, device memory). The topology
is described inside a fixture, never while a module is imported, and
the persistent compilation cache is off around these compiles (an entry
written for a described chip cannot be read back without one)."""
import dataclasses
import functools
import os
import re

import jax
import jax.numpy as jnp
import pytest
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec, \
    SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.ann_topk import ops as ann_ops
from repro.kernels.cuckoo_probe import ops as cuckoo_ops
from repro.models import model as M
from repro.parallel.sharding import params_shardings, serve_rules, \
    single_device_rules


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    log_dir = os.environ.get("TPU_LOG_DIR")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                       # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", cache_on)
    if log_dir is None:
        os.environ.pop("TPU_LOG_DIR", None)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def test_cuckoo_probe_compiles_for_v5e(one_chip):
    """2^22 buckets x 8 slots (256 MiB of keys + values), 65536 lookups."""
    rows = (1 << 22) * 8 // 128
    table = _spec(one_chip, (rows, 128), jnp.int32)
    c = cuckoo_ops._probe.lower(
        _spec(one_chip, (65536,), jnp.int32), table, table,
        n_buckets=1 << 22, slots=8, interpret=False).compile()
    assert "tpu_custom_call" in c.as_text()
    # the lane-dense table is read in place, never relaid out
    assert c.memory_analysis().temp_size_in_bytes < 64 << 20


def test_ann_topk_compiles_for_v5e(one_chip):
    """2^20 x 128 f32 reduced vectors (512 B rows), top-64 of 256 queries."""
    c = ann_ops._topk.lower(
        _spec(one_chip, (256, 128), jnp.float32),
        _spec(one_chip, (1 << 20, 128), jnp.float32),
        k=64, block_q=128, tile=512, interpret=False).compile()
    assert "tpu_custom_call" in c.as_text()


def _served_decode_step(topo, one_chip, **jit_kw):
    """The served model's decode step at 8 slots x 8192 bf16 KV, compiled
    for one described chip; (compiled, cache bytes)."""
    cfg = dataclasses.replace(get_config("mistral-nemo-12b"), n_groups=8)
    rules = single_device_rules(topo.devices[0])

    def on_chip(tree):
        return jax.tree.map(
            lambda a: _spec(one_chip, a.shape, a.dtype), tree)

    params = on_chip(jax.eval_shape(
        lambda k: jax.tree.map(lambda a: a.astype(jnp.bfloat16),
                               M.init_params(k, cfg)[0]),
        jax.random.PRNGKey(0)))
    cache = on_chip(jax.eval_shape(
        lambda: M.init_cache(cfg, 8, 8192, dtype=jnp.bfloat16)))
    step = jax.jit(functools.partial(M.decode_step, cfg=cfg, rules=rules,
                                     compute_dtype=jnp.bfloat16), **jit_kw)
    c = step.lower(params, token=_spec(one_chip, (8, 1), jnp.int32),
                   cache=cache,
                   index=_spec(one_chip, (8,), jnp.int32)).compile()
    return c, sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(cache))


def test_served_decode_step_compiles_for_v5e(topo, one_chip):
    """One decode step of the served model: 8 slots x 8192 bf16 KV."""
    c, _ = _served_decode_step(topo, one_chip)
    hlo = c.as_text()
    assert "f64[" not in hlo and "s64[" not in hlo
    mem = c.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert used < 16e9, used


def test_served_decode_step_writes_its_cache_in_place_on_v5e(topo,
                                                             one_chip):
    """Donated as the engine donates it, the step's output cache is its
    input cache, and no temporary holds a layer's K/V (a layout that
    copied the stack would need 2.15 GB)."""
    c, cache_bytes = _served_decode_step(topo, one_chip,
                                         donate_argnames="cache")
    mem = c.memory_analysis()
    assert mem.alias_size_in_bytes == cache_bytes
    assert mem.temp_size_in_bytes < 64 << 20, mem.temp_size_in_bytes


def test_served_moe_step_compiles_for_v5e(topo, one_chip):
    """qwen3-moe-235b-a22b at published widths as one expert-parallel
    rank (12 layers, experts [0, 8) of 128 held): the donated decode
    step at 24 slots x 4096 bf16 KV aliases its cache and runs its
    experts as grouped-matmul kernels, and a prefill of the largest
    prompt bucket fits beside the weights and two caches."""
    from repro.models.config import MoeSpec
    base = get_config("qwen3-moe-235b-a22b")
    cfg = dataclasses.replace(base, n_groups=12, pattern=tuple(
        tuple(dataclasses.replace(s, n_held=8)
              if isinstance(s, MoeSpec) else s for s in layer)
        for layer in base.pattern))
    rules = single_device_rules(topo.devices[0])

    def on_chip(tree):
        return jax.tree.map(
            lambda a: _spec(one_chip, a.shape, a.dtype), tree)

    params = on_chip(jax.eval_shape(
        lambda k: jax.tree.map(lambda a: a.astype(jnp.bfloat16),
                               M.init_params(k, cfg)[0]),
        jax.random.PRNGKey(0)))
    cache = on_chip(jax.eval_shape(
        lambda: M.init_cache(cfg, 24, 4096, dtype=jnp.bfloat16)))
    step = jax.jit(functools.partial(
        M.decode_step, cfg=cfg, rules=rules, compute_dtype=jnp.bfloat16,
        counts=True), donate_argnames="cache")
    c = step.lower(params, token=_spec(one_chip, (24, 1), jnp.int32),
                   cache=cache, index=_spec(one_chip, (24,), jnp.int32),
                   active=_spec(one_chip, (24,), jnp.bool_)).compile()
    cache_bytes = sum(a.size * a.dtype.itemsize
                      for a in jax.tree.leaves(cache))
    mem = c.memory_analysis()
    assert mem.alias_size_in_bytes == cache_bytes
    assert mem.temp_size_in_bytes < 64 << 20, mem.temp_size_in_bytes
    assert "ragged-dot" in c.as_text()
    pre = jax.jit(functools.partial(
        M.prefill, cfg=cfg, rules=rules, compute_dtype=jnp.bfloat16))
    c = pre.lower(params,
                  batch={"tokens": _spec(one_chip, (1, 2048), jnp.int32)},
                  cache=on_chip(jax.eval_shape(lambda: M.init_cache(
                      cfg, 1, 4096, dtype=jnp.bfloat16))),
                  last_index=_spec(one_chip, (), jnp.int32)).compile()
    mem = c.memory_analysis()
    weights = sum(a.size * 2 for a in jax.tree.leaves(params))
    assert weights + 2 * cache_bytes + mem.temp_size_in_bytes < 14e9


# (config at published widths, mesh (data, model)): which cache axes the
# serve rules shard; granite-20b has one kv head, so "model" falls to
# the sequence
MESHES = {
    "seq": ("granite-20b", (1, 4)),
    "batch": ("mistral-nemo-12b", (4, 1)),
    "seq_and_batch": ("granite-20b", (2, 2)),
    "heads": ("mistral-nemo-12b", (1, 4)),
    "heads_and_batch": ("mistral-nemo-12b", (2, 2)),
}

_COLLECTIVE = re.compile(r"= (.*?) (?:all-gather|all-to-all|all-reduce|"
                         r"reduce-scatter|collective-permute)(?:-start)?\(")
_SHAPE = re.compile(r"(bf16|f32|s32|u32|pred|s8)\[([\d,]*)\]")
_BYTES = {"bf16": 2, "f32": 4, "s32": 4, "u32": 4, "pred": 1, "s8": 1}


@pytest.mark.parametrize("case", list(MESHES))
def test_sharded_decode_step_writes_its_cache_in_place_on_v5e(topo, case):
    """On a cache sharded by slot, sequence or kv head the donated step's
    row scatter stays local: the cache is aliased, no collective moves
    as much as one layer's shard of K, and no temporary holds one."""
    name, shape = MESHES[case]
    cfg = dataclasses.replace(get_config(name), n_groups=4)
    mesh = Mesh(np.array(topo.devices).reshape(shape), ("data", "model"))
    rules = serve_rules(mesh)
    logical = {}

    def init(k):
        p, logical["params"] = M.init_params(k, cfg)
        return jax.tree.map(lambda a: a.astype(jnp.bfloat16), p)

    aparams = jax.eval_shape(init, jax.random.PRNGKey(0))
    acache = jax.eval_shape(
        lambda: M.init_cache(cfg, 8, 8192, dtype=jnp.bfloat16))
    cache_sh = jax.tree.map(
        lambda names, a: rules.sharding(a.shape, names),
        M.cache_logical_tree(cfg), acache,
        is_leaf=lambda x: isinstance(x, tuple))
    placed = functools.partial(jax.tree.map, lambda a, s: _spec(
        s, a.shape, a.dtype))
    rep = NamedSharding(mesh, PartitionSpec())
    step = jax.jit(functools.partial(M.decode_step, cfg=cfg, rules=rules,
                                     compute_dtype=jnp.bfloat16),
                   donate_argnames="cache", out_shardings=(cache_sh, None))
    c = step.lower(
        placed(aparams, params_shardings(rules, aparams, logical["params"])),
        token=_spec(rep, (8, 1), jnp.int32),
        cache=placed(acache, cache_sh),
        index=_spec(rep, (8,), jnp.int32)).compile()

    k = next(iter(acache["groups"].values()))["k"]
    k_sh = next(iter(cache_sh["groups"].values()))["k"]
    layer_shard = (np.prod(k_sh.shard_shape(k.shape)[1:])
                   * k.dtype.itemsize)
    split = {n for n, ax in zip(("batch", "heads", "seq"), k_sh.spec[1:4])
             if ax is not None and rules.axis_size(ax) > 1}
    assert split == set(case.split("_and_")), split
    mem = c.memory_analysis()
    cache_shard = sum(np.prod(s.shard_shape(a.shape)) * a.dtype.itemsize
                      for a, s in zip(jax.tree.leaves(acache),
                                      jax.tree.leaves(cache_sh)))
    assert mem.alias_size_in_bytes == cache_shard
    assert mem.temp_size_in_bytes < layer_shard, mem.temp_size_in_bytes
    moved = [int(np.prod([int(d) for d in dims.split(",") if d]))
             * _BYTES[dt] for m in _COLLECTIVE.finditer(c.as_text())
             for dt, dims in _SHAPE.findall(m.group(1))]
    # a split "model" axis reduces over it, so the pattern must find some
    assert moved or shape[1] == 1
    assert max(moved, default=0) < layer_shard, max(moved)
