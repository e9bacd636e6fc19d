"""The sparse-expert architecture (`bench/arch/moe.py`) at the registry's
reduced qwen3-moe size, holding experts [4, 8) of 8: its weights have
the program's layout, its float32 forward agrees with what the engine
serves through prefill and then decode through its cache, its work
counts follow the engine's expert counters, its readers read a trace,
and a cell of it runs through the harness."""
import copy
import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import arch, harness, reference
from bench.tests import tiny

BENCH = pathlib.Path(__file__).resolve().parents[1]

# the widths of `get_config("qwen3-moe-235b-a22b", reduced=True)`; this
# rank holds experts [4, 8) of the router's 8
CONFIG = {
    "name": "tiny-moe", "registry": "qwen3-moe-235b-a22b", "arch": "moe",
    "hidden_size": 64, "num_hidden_layers": 2, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "moe_intermediate_size": 96,
    "num_experts": 4, "num_experts_per_tok": 2, "router_experts": 8,
    "first_expert": 4, "norm_topk_prob": True, "vocab_size": 512,
    "rope_theta": 10000.0, "rms_norm_eps": 1e-6,
    "tie_word_embeddings": False,
    "serving": {"slots": 3, "max_len": 160, "dtype": "bfloat16",
                "pause_idle_steps": 4, "step_time_s": 0.02,
                "tau_hot_s": 0.05, "tau_be_s": 1.0},
}
QWEN3 = json.loads((BENCH / "configs" / "qwen3-moe-235b-a22b.json")
                   .read_text())
IS_SHAPE = (lambda s: isinstance(s, tuple)
            and all(isinstance(e, int) for e in s))


def f32(cfg=CONFIG):
    cfg = copy.deepcopy(cfg)
    cfg["serving"]["dtype"] = "float32"
    return cfg


@pytest.mark.parametrize("cfg", [CONFIG, QWEN3], ids=["tiny", "qwen3"])
def test_weights_have_the_programs_layout(cfg):
    from repro.models import model as M
    prog = jax.eval_shape(
        lambda k: M.init_params(k, arch.of(cfg).program_config(cfg))[0],
        jax.random.PRNGKey(0))
    ours = arch.of(cfg).param_shapes(cfg)
    assert jax.tree.structure(ours, is_leaf=IS_SHAPE) == \
        jax.tree.structure(prog)
    assert jax.tree.leaves(ours, is_leaf=IS_SHAPE) == \
        [a.shape for a in jax.tree.leaves(prog)]


def test_the_full_configuration_holds_its_share():
    mod = arch.of(QWEN3)
    (attn, moe), = mod.program_config(QWEN3).pattern
    assert (moe.n_experts, moe.first, moe.n_held, moe.top_k, moe.d_ff) == \
        (128, 0, 8, 8, 1536)
    assert attn.qk_norm and (attn.n_heads, attn.n_kv) == (64, 4)
    n = sum(int(np.prod(s)) for s in jax.tree.leaves(
        mod.param_shapes(QWEN3), is_leaf=IS_SHAPE))
    assert 3.91e9 < n < 3.93e9


def served(cfg, params, prompts, steps):
    """Serve `prompts` together through the engine (one slot left idle):
    each request's served tokens and the logits rows that chose them."""
    from repro.parallel.sharding import single_device_rules
    from repro.serving.engine import DecodeEngine, Request
    sv = cfg["serving"]
    eng = DecodeEngine(arch.of(cfg).program_config(cfg), params,
                       single_device_rules(jax.devices()[0]),
                       max_slots=sv["slots"], max_len=sv["max_len"],
                       compute_dtype=jnp.dtype(sv["dtype"]))
    probe = harness.Probe(eng, annotate=False)
    probe.keep = {f"r{i}" for i in range(len(prompts))}
    reqs = [Request(f"r{i}", p, max_new=steps + 1)
            for i, p in enumerate(prompts)]
    for r in reqs:
        eng.admit(r)
    for _ in range(steps):
        eng.step()
    return [(r.generated, np.stack(probe.rows[r.rid])) for r in reqs], \
        eng.counters


def test_the_engine_serves_what_the_float32_forward_computes():
    """Prefill (prompts padded to their bucket), then decode through the
    cache with an idle slot beside, in float32: each served row within
    1e-4 of the reference (f32 rounding over two layers; a wrong gate,
    a dropped choice or a pad's work would move it by 1e-2 or more)."""
    cfg = f32()
    params = reference.init_params(7, cfg, jnp.float32)
    rng = np.random.default_rng(3)
    prompts = [rng.integers(1, cfg["vocab_size"], n).astype(np.int32)
               for n in (37, 20)]
    got, counters = served(cfg, params, prompts, steps=12)
    for prompt, (tokens, rows) in zip(prompts, got):
        res = reference.compare(params, cfg, prompt, tokens, rows)
        assert res["program"]["logit_err"] < 1e-4, res
        assert res["program"]["token_gap"] < 1e-4, res
    # two decoding tokens, 2 layers, 12 steps, 2 choices each
    assert 0 < counters["moe_pairs"] <= 12 * 2 * 2 * 2
    assert 0 < counters["moe_experts_reached"] <= counters["moe_pairs"]


def test_the_fp8_control_is_far_from_the_program():
    cfg = f32()
    params = reference.init_params(7, cfg, jnp.float32)
    prompt = np.random.default_rng(4).integers(
        1, cfg["vocab_size"], 30).astype(np.int32)
    (tokens, rows), = served(cfg, params, [prompt], steps=10)[0]
    res = reference.compare(params, cfg, prompt, tokens, rows, ("fp8",))
    assert res["fp8"]["logit_err"] > 100 * res["program"]["logit_err"]


def test_work_counts_follow_the_expert_counters():
    mod = arch.of(QWEN3)
    lengths = [500, 1000, 3000]
    base = mod.decode_step(QWEN3, lengths)
    one = mod.decode_step(QWEN3, lengths, reached=1, pairs=1)
    expert = 3 * 4096 * 1536
    assert one["bytes"] - base["bytes"] == expert * 2
    assert one["flops"] - base["flops"] == 2 * expert
    # attention, router and both norms read once a layer; the head once
    attn = 4096 * 64 * 128 * 2 + 2 * 4096 * 4 * 128
    weights = 12 * (attn + 4096 * 128 + 2 * 128 + 2 * 4096) + 4096 \
        + 151936 * 4096
    kv = 12 * 2 * 4 * 128 * (sum(lengths) + 3)
    io = 3 * 4096 + 3 * 151936
    assert base["bytes"] == 2 * (weights + kv + io)
    steps = [lengths, [7], [10, 20]]
    win = mod.decode_window(QWEN3, steps, reached=40, pairs=90)
    want = [mod.decode_step(QWEN3, s, r, p) for s, r, p in
            zip(steps, (10, 20, 10), (30, 40, 20))]
    for k in ("flops", "bytes"):
        assert win[k] == sum(w[k] for w in want)


def fake_run(cfg, steps, seconds, counters):
    return harness.Run(
        "qwen3moe.reasoning", cfg, {}, 50.0, 0.0, 50.0, [], {}, [],
        [(1.0 + i, 1.5 + i, s) for i, s in enumerate(steps)], 0.0,
        "TPU v5 lite",
        {"programs_by_host": {"step": {"count": len(steps),
                                       "seconds": seconds}}},
        None, counters)


def test_the_readers_read_the_trace_and_the_counters():
    bench = harness.Bench(BENCH.parent)
    roof, mfu = (bench.reader(n) for n in ("moe_decode_roofline",
                                           "moe_decode_mfu"))
    steps = [[400 + i] * 24 for i in range(100)]
    counters = {"moe_experts_reached": 100 * 12 * 6,
                "moe_pairs": 100 * 12 * 12, "d2h_bytes": 0}
    mod = arch.of(QWEN3)
    work = mod.decode_window(QWEN3, steps, 100 * 12 * 6, 100 * 12 * 12)
    least = work["bytes"] / 819e9        # memory-bound at this batch
    run = fake_run(QWEN3, steps, 2 * least, counters)
    assert roof(run) == pytest.approx(50.0)
    assert mfu(run) == pytest.approx(
        100 * work["flops"] / (2 * least) / 197e12)
    # without the counters, as on a model without MoE layers: nothing
    dense = fake_run(QWEN3, steps, 2 * least, {"d2h_bytes": 0})
    assert roof(dense) is None and mfu(dense) is None


# in bfloat16 at these widths (top-2 of 8 experts over 64 features) a
# router choice that flips on rounding moves a row's logits by up to
# 0.76 of their norm, and the fp8 control reads 0.14-0.28 (CPU, seed
# 11): the two do not separate there, so the cell is served in float32,
# where the program stays within 1e-4 of the reference
LIMITS = {"logit_err": {"limit": 1e-3}, "token_gap": {"limit": 1e-3},
          "sample_tokens": 120, "sample_requests": 8}


def make_root(tmp):
    """tiny's root with a float32 cell of the reduced qwen3-moe
    configuration, listed in every metric that lists cells."""
    root = tiny.make_root(tmp)
    b = root / "bench"
    (b / "configs" / "tiny-moe.json").write_text(json.dumps(f32()))
    (b / "limits" / "tiny-moe.chat.json").write_text(json.dumps(LIMITS))
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "tiny-moe", "source": "test",
                            "file": "bench/configs/tiny-moe.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "tiny-moe.chat", "config": "tiny-moe",
                              "traffic": "tiny_chat", "chips": 1,
                              "why": "test"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "workloads" in m:
            m["workloads"].append("tiny-moe.chat")
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root


def test_a_cell_runs_through_the_harness(tmp_path):
    root = make_root(tmp_path)
    res = harness.run(root, "tiny-moe.chat", 11, 1.0, True)
    line = harness.result_line(harness.Bench(root), res, True)
    assert line["correct"] is True, line["checks"]
    n = res["run"].engine_counters
    assert n["moe_pairs"] > 0 and n["moe_experts_reached"] > 0
    assert "d2h_kb_per_token" in line["metrics"]
