"""Serving-path correctness: prefill + token-by-token decode must produce
the same logits as the parallel (train-mode) forward pass, for every
mixer family (attention / GQA / MQA / cross-attn / mamba2 / mLSTM / sLSTM).

This exercises every cache mechanism: KV write/read, select-based decode
updates, conv states, SSD recurrent states, and the zamba shared-attention
cache."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import model as M
from repro.parallel.sharding import single_device_rules

ARCHS = ["deepseek-7b", "gemma-2b", "qwen3-moe-235b-a22b", "zamba2-7b",
         "xlstm-350m", "whisper-medium", "qwen2-vl-2b"]


@pytest.fixture(scope="module")
def rules():
    return single_device_rules()


def _no_drop(cfg):
    """Raise MoE capacity so no token is ever dropped: the capacity is a
    function of the *call's* token count, so prefill(S0) and forward(S)
    drop different tokens at finite capacity — by design (GShard)."""
    import dataclasses
    from repro.models.config import MoeSpec

    def fix(layer):
        return tuple(dataclasses.replace(s, capacity_factor=64.0)
                     if isinstance(s, MoeSpec) else s for s in layer)

    return dataclasses.replace(
        cfg, pattern=tuple(fix(l) for l in cfg.pattern),
        tail=tuple(fix(l) for l in cfg.tail))


@pytest.mark.parametrize("arch", ARCHS)
def test_decode_matches_forward(arch, rules):
    cfg = _no_drop(get_config(arch, reduced=True))
    B, S = 2, 12
    key = jax.random.PRNGKey(0)
    params, _ = M.init_params(key, cfg)
    # f32 compute for a tight comparison
    dt = jnp.float32

    tokens = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0, cfg.vocab)
    batch = {"tokens": tokens}
    if cfg.encoder is not None:
        batch["frames"] = jax.random.normal(
            jax.random.PRNGKey(2), (B, cfg.encoder.n_frames, cfg.d_model),
            dt) * 0.1
    if cfg.modality == "vlm":
        # keep it text-only for equivalence (vision path tested in smoke)
        pass

    logits_par, _ = M.forward(params, cfg, rules, batch, compute_dtype=dt,
                              remat=False)

    # prefill on the first S0 tokens, then decode the rest one by one
    S0 = 5
    cache = M.init_cache(cfg, B, S, dtype=dt)
    cache, logits_pre = M.prefill(
        params, cfg, rules, dict(batch, tokens=tokens[:, :S0]), cache,
        compute_dtype=dt)
    np.testing.assert_allclose(
        np.asarray(logits_pre), np.asarray(logits_par[:, S0 - 1]),
        rtol=2e-4, atol=2e-4)

    for t in range(S0, S):
        cache, logits_dec = M.decode_step(
            params, cfg, rules, tokens[:, t:t + 1], cache,
            jnp.asarray(t, jnp.int32), compute_dtype=dt)
        np.testing.assert_allclose(
            np.asarray(logits_dec), np.asarray(logits_par[:, t]),
            rtol=5e-4, atol=5e-4,
            err_msg=f"{arch}: decode step {t} diverged")


@pytest.mark.parametrize("arch", ["mistral-nemo-12b", "gemma-2b",
                                  "qwen3-moe-235b-a22b", "zamba2-7b",
                                  "xlstm-350m"])
def test_reference_logits_match_float32_forward(arch, rules):
    """The layer-at-a-time float32 reference equals `forward` run in
    float32 on the same bf16-rounded weights."""
    cfg = _no_drop(get_config(arch, reduced=True))
    params, _ = M.init_params(jax.random.PRNGKey(0), cfg)
    bf16 = jax.tree.map(lambda a: a.astype(jnp.bfloat16), params)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 12), 0,
                                cfg.vocab)
    want, _ = M.forward(jax.tree.map(lambda a: a.astype(jnp.float32), bf16),
                        cfg, rules, {"tokens": tokens},
                        compute_dtype=jnp.float32, remat=False)
    got = M.reference_logits(bf16, cfg, rules, tokens,
                             vocab_chunk=cfg.vocab // 3)
    assert got.dtype == jnp.float32 and got.shape == want.shape
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)
