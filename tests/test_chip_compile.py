"""Compiles the main path for one TPU v5e chip that is described, not
attached: the case-study kernels at the smoke widths and the served
decode step of mistral-nemo-12b at published widths (8 of 40 layers).

Nothing runs; the TPU compiler refuses here what it would refuse on the
chip (block tiling, scoped VMEM, dtypes, device memory). The topology
is described inside a fixture, never while a module is imported, and
the persistent compilation cache is off around these compiles (an entry
written for a described chip cannot be read back without one)."""
import dataclasses
import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.ann_topk import ops as ann_ops
from repro.kernels.cuckoo_probe import ops as cuckoo_ops
from repro.models import model as M
from repro.parallel.sharding import single_device_rules


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


def test_served_decode_step_compiles_for_v5e(topo, one_chip):
    """One decode step of the served model: 8 slots x 8192 bf16 KV."""
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
                                     compute_dtype=jnp.bfloat16))
    c = step.lower(params, token=_spec(one_chip, (8, 1), jnp.int32),
                   cache=cache,
                   index=_spec(one_chip, (8,), jnp.int32)).compile()
    hlo = c.as_text()
    assert "f64[" not in hlo and "s64[" not in hlo
    mem = c.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert used < 16e9, used
