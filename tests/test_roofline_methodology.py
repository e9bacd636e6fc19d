"""Validates the roofline cost-extrapolation methodology and the sharding
rules' invariants."""
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

import jax
import jax.numpy as jnp

from repro.launch.roofline import (CostTerms, collective_wire_bytes,
                                   extrapolate, roofline)


def test_probe_extrapolation_matches_full_unroll():
    """total(G) = probe(1) + (G-1) * marginal must equal a fully unrolled
    compile of the same G-layer stack (the scan-body-once workaround)."""
    D, G = 64, 5

    def make(n, unroll):
        def step(x, ws):
            if unroll:
                for i in range(n):
                    x = jnp.tanh(x @ ws[i])
                return x.sum()
            def body(c, w):
                return jnp.tanh(c @ w), None
            y, _ = jax.lax.scan(body, x, ws)
            return y.sum()
        xs = jax.ShapeDtypeStruct((32, D), jnp.float32)
        ws = jax.ShapeDtypeStruct((n, D, D), jnp.float32)
        c = jax.jit(step, static_argnames=()).lower(xs, ws).compile()
        ca = c.cost_analysis()
        return CostTerms(float(ca.get("flops", 0)),
                         float(ca.get("bytes accessed", 0)), 0.0, {})

    p1 = make(1, unroll=True)
    p2 = make(2, unroll=True)
    full = make(G, unroll=True)
    est = extrapolate(p1, p2, G)
    assert abs(est.flops - full.flops) / full.flops < 0.02, \
        (est.flops, full.flops)
    # and the scanned compile undercounts, which is WHY we extrapolate
    scanned = make(G, unroll=False)
    assert scanned.flops < 0.5 * full.flops


def test_collective_wire_parsing():
    text = """
  %ag = f32[64,128]{1,0} all-gather(%x), replica_groups=[2,4]<=[8]
  %ar = bf16[32,32]{1,0} all-reduce(%y), replica_groups=[1,8]<=[8]
  %rs = f32[8,16]{1,0} reduce-scatter(%z), replica_groups=[2,4]<=[8]
  %cp = f32[16]{0} collective-permute(%w), replica_groups=[8,1]<=[8]
  %done = f32[64,128]{1,0} all-gather-done(%t)
"""
    wires = collective_wire_bytes(text)
    assert wires["all-gather"] == pytest.approx(64 * 128 * 4 * 3 / 4)
    assert wires["all-reduce"] == pytest.approx(2 * 32 * 32 * 2 * 7 / 8)
    assert wires["reduce-scatter"] == pytest.approx(8 * 16 * 4 * 3)
    # groups of size 1 contribute nothing; -done lines are not re-counted
    assert "collective-permute" not in wires or \
        wires["collective-permute"] == 0.0


def test_roofline_terms_and_dominance():
    t = CostTerms(flops=1.97e14, bytes_accessed=819e9 * 2.0,
                  wire_bytes=50e9 * 3.0, wire_by_kind={})
    r = roofline(t, chips=256, model_flops=256 * 0.5 * 1.97e14)
    assert r["t_compute"] == pytest.approx(1.0)
    assert r["t_memory"] == pytest.approx(2.0)
    assert r["t_collective"] == pytest.approx(3.0)
    assert r["dominant"] == "collective"
    assert r["roofline_fraction"] == pytest.approx(0.5 / 3.0)


# ---------------------------------------------------------------------------
# sharding rules invariants
# ---------------------------------------------------------------------------

from repro.parallel.sharding import train_rules  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402


@pytest.fixture(scope="module")
def rules():
    return train_rules(make_mesh((1, 1), ("data", "model")))


NAMES = [None, "batch", "embed", "heads", "kv_heads", "ffn", "experts",
         "vocab", "res_embed", "act_qr", "layers"]


@settings(max_examples=60, deadline=None)
@given(dims=st.lists(st.tuples(st.integers(1, 64),
                               st.sampled_from(NAMES)), min_size=1,
                     max_size=5))
def test_spec_never_reuses_axis_and_always_divides(dims):
    # AbstractMesh: Rules only reads shape/axis names, no devices needed
    mesh = jax.sharding.AbstractMesh((2, 4), ("data", "model"))
    r = train_rules(mesh)
    shape = [d for d, _ in dims]
    names = [n for _, n in dims]
    spec = r.spec_for_shape(shape, names)
    used = []
    for dim, part in zip(shape, spec):
        if part is None:
            continue
        parts = part if isinstance(part, tuple) else (part,)
        size = 1
        for a in parts:
            assert a not in used, f"axis {a} reused in {spec}"
            used.append(a)
            size *= mesh.shape[a]
        assert dim % size == 0, (dim, part, spec)
