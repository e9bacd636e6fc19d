"""The served MoE layer at the registry's reduced qwen3-moe size: a layer
told which experts it holds computes their part of the output for every
token routed to them, drops none whatever the skew, and counts its work
in the engine's counters; the shares of all ranks add up to the uncut
layer. A model without MoE layers serves the same program as before."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import model as M
from repro.models import moe
from repro.models.config import MoeSpec
from repro.models.layers import Ctx
from repro.parallel.sharding import single_device_rules
from repro.serving.engine import DecodeEngine, Request

F32 = jnp.float32


@pytest.fixture(scope="module")
def rules():
    return single_device_rules()


def held(cfg, first, n):
    """`cfg` with every MoE layer holding experts [first, first + n)."""
    return dataclasses.replace(cfg, pattern=tuple(
        tuple(dataclasses.replace(s, first=first, n_held=n)
              if isinstance(s, MoeSpec) else s for s in layer)
        for layer in cfg.pattern))


def layer_setup(seed=0, tokens=24):
    cfg = get_config("qwen3-moe-235b-a22b", reduced=True)
    (_, spec), = cfg.pattern
    params, _ = moe.init(jax.random.PRNGKey(seed), cfg, spec)
    x = jax.random.normal(jax.random.PRNGKey(seed + 1),
                          (2, tokens // 2, cfg.d_model), F32)
    return cfg, spec, params, x


def share(params, first, n):
    return {k: v if k == "router" else v[first:first + n]
            for k, v in params.items()}


def run(params, x, spec, cfg, rules, mode="prefill"):
    ctx = Ctx(rules=rules, mode=mode, positions=None, compute_dtype=F32)
    with jax.default_matmul_precision("highest"):
        return np.asarray(moe.apply(params, x, spec, cfg, ctx)), ctx.counts


def per_token(params, x, spec, first=0):
    """Each token alone: softmax over all experts, top-k, gates
    renormalised, and the sum of gate * SwiGLU expert over the chosen
    experts held from `first` on."""
    p = jax.tree.map(lambda a: np.asarray(a, np.float64), params)
    out = np.zeros(x.shape, np.float64)
    for b, s in np.ndindex(x.shape[:2]):
        h = np.asarray(x[b, s], np.float64)
        z = h @ p["router"]
        prob = np.exp(z - z.max())
        prob /= prob.sum()
        top = np.argsort(-prob, kind="stable")[:spec.top_k]
        gate = prob[top] / prob[top].sum()
        for e, g in zip(top, gate):
            if first <= e < first + p["w_in"].shape[0]:
                i = e - first
                a = h @ p["w_gate"][i]
                u = h @ p["w_in"][i]
                out[b, s] += g * ((a / (1 + np.exp(-a)) * u)
                                  @ p["w_out"][i])
    return out


@pytest.mark.parametrize("n", [1, 2, 4])
def test_shares_add_up_to_the_uncut_layer(rules, n):
    cfg, spec, params, x = layer_setup()
    whole, _ = run(params, x, spec, cfg, rules)
    parts = [run(share(params, f, n), x,
                 dataclasses.replace(spec, first=f, n_held=n), cfg,
                 rules)[0] for f in range(0, spec.n_experts, n)]
    np.testing.assert_allclose(sum(parts), whole, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(whole, per_token(params, x, spec),
                               rtol=1e-4, atol=1e-5)


def test_no_token_is_dropped_where_the_capacity_path_drops(rules):
    """Every token routed first to expert 0: 24 tokens on one expert
    whose training capacity is ceil(1.25 * 24 * 2 / 8) = 8."""
    cfg, spec, params, x = layer_setup()
    x = jnp.abs(x)
    params = dict(params, router=params["router"].at[:, 0].set(5.0))
    spec4 = dataclasses.replace(spec, first=0, n_held=4)
    got, counts = run(share(params, 0, 4), x, spec4, cfg, rules)
    want = per_token(params, x, spec, first=0)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)
    assert int(counts["moe_experts_reached"]) >= 1
    # the same tokens through training's static capacity lose most of
    # expert 0's part
    trained, _ = run(params, x, spec, cfg, rules, mode="train")
    full = per_token(params, x, spec)
    lost = np.abs(trained - full).max(axis=-1).reshape(-1) > 1e-3
    assert lost.sum() >= 24 - 8


def test_padding_and_idle_tokens_get_no_work(rules):
    cfg, spec, params, x = layer_setup()
    mask = np.zeros((2, 12), bool)
    mask[0, :5] = mask[1, :9] = True
    ctx = Ctx(rules=rules, mode="prefill", positions=None,
              compute_dtype=F32, token_mask=jnp.asarray(mask))
    with jax.default_matmul_precision("highest"):
        got = np.asarray(moe.apply(params, x, spec, cfg, ctx))
    want = per_token(params, x, spec)
    np.testing.assert_allclose(got[mask], want[mask], rtol=1e-4, atol=1e-5)
    assert not got[~mask].any()
    assert int(ctx.counts["moe_pairs"]) == 14 * spec.top_k


def test_training_refuses_a_layer_that_holds_part_of_the_experts(rules):
    cfg, spec, params, x = layer_setup()
    with pytest.raises(ValueError, match="holds 4"):
        run(share(params, 0, 4), x,
            dataclasses.replace(spec, n_held=4), cfg, rules, mode="train")


def engine_for(cfg, rules, seed=0, slots=3):
    params, _ = M.init_params(jax.random.PRNGKey(seed), cfg)
    return DecodeEngine(cfg, params, rules, max_slots=slots, max_len=64,
                        compute_dtype=F32), params


@pytest.mark.parametrize("first", [0, 4])
def test_counters_count_a_hand_made_routing(rules, first):
    """A zero router ties every expert, and top-k then takes experts 0
    and 1 for every token: held [0, 4) computes 2 pairs a decoding token
    a layer and reaches 2 experts a layer; held [4, 8) none. The third
    slot stays idle and is not counted."""
    cfg = held(get_config("qwen3-moe-235b-a22b", reduced=True), first, 4)
    eng, params = engine_for(cfg, rules)
    groups = dict(params["groups"])
    groups["L0S1"] = dict(groups["L0S1"], mixer=dict(
        groups["L0S1"]["mixer"],
        router=jnp.zeros_like(groups["L0S1"]["mixer"]["router"])))
    eng.params = jax.device_put(dict(params, groups=groups))
    rng = np.random.default_rng(0)
    for i in range(2):
        eng.admit(Request(f"r{i}", rng.integers(1, cfg.vocab, 7 + i)
                          .astype(np.int32), max_new=64))
    before = eng.counters
    steps = 5
    for _ in range(steps):
        eng.step()
    got = {k: v - before[k] for k, v in eng.counters.items()}
    layers = cfg.n_groups
    want_pairs = steps * layers * 2 * 2 if first == 0 else 0
    want_reached = steps * layers * 2 if first == 0 else 0
    assert got["moe_pairs"] == want_pairs
    assert got["moe_experts_reached"] == want_reached
    assert got["d2h_bytes"] == steps * 3 * cfg.vocab * 4


def test_a_model_without_moe_serves_the_program_it_served(rules):
    cfg = get_config("mistral-nemo-12b", reduced=True)
    eng, _ = engine_for(cfg, rules)
    eng.admit(Request("r", np.arange(1, 9, dtype=np.int32), max_new=4))
    args = dict(token=jnp.zeros((3, 1), jnp.int32), index=jnp.asarray(
        eng.lengths))
    out = eng._decode(eng.params, cache=eng.cache, **args)
    eng.cache = out[0]
    assert len(out) == 2
    assert set(eng.counters) == {"d2h_bytes"}
    jaxpr = jax.make_jaxpr(lambda p, c: M.decode_step(
        p, cfg, rules, args["token"], c, args["index"],
        compute_dtype=F32))(eng.params, eng.cache)
    assert len(jaxpr.out_avals) == len(jax.tree.leaves(eng.cache)) + 1


def test_served_layer_on_an_expert_parallel_mesh_matches_one_device():
    """Prefill and decode on a 2x4 mesh (experts over "model", by either
    serving layout) give one device's logits and token-expert pairs."""
    import os
    import pathlib
    import subprocess
    import sys
    import textwrap
    repo = pathlib.Path(__file__).resolve().parents[1]
    env = dict(os.environ, PYTHONPATH=str(repo / "src"),
               XLA_FLAGS="--xla_force_host_platform_device_count=8")
    body = """
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_config
        from repro.launch.mesh import make_mesh
        from repro.parallel.sharding import serve_rules, single_device_rules
        from repro.models import model as M
        cfg = get_config("qwen3-moe-235b-a22b", reduced=True)
        params, _ = M.init_params(jax.random.PRNGKey(0), cfg)
        toks = jax.random.randint(jax.random.PRNGKey(1), (2, 12), 0,
                                  cfg.vocab)

        def serve(rules):
            cache = M.init_cache(cfg, 2, 12, dtype=jnp.float32)
            cache, out = M.prefill(params, cfg, rules,
                                   {"tokens": toks[:, :6]}, cache,
                                   compute_dtype=jnp.float32)
            rows, pairs = [out], 0
            for t in range(6, 12):
                cache, out, n = M.decode_step(
                    params, cfg, rules, toks[:, t:t + 1], cache,
                    jnp.asarray(t, jnp.int32), compute_dtype=jnp.float32,
                    active=jnp.array([True, t < 9]), counts=True)
                rows.append(out)
                pairs += int(n["moe_pairs"])
            return np.stack(rows), pairs

        want, want_pairs = serve(single_device_rules())
        mesh = make_mesh((2, 4), ("data", "model"))
        for tokens in (False, True):
            got, pairs = serve(serve_rules(mesh, moe_tokens_gather=tokens))
            np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
            assert pairs == want_pairs == 2 * 9 * 2, (pairs, want_pairs)
        print("MESH OK")
        """
    proc = subprocess.run([sys.executable, "-c", textwrap.dedent(body)],
                          capture_output=True, text=True, timeout=600,
                          env=env)
    assert "MESH OK" in proc.stdout, proc.stderr[-4000:]
