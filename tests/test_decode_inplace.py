"""The decode step writes its new K/V rows in place, into a cache the
engine donates: the in-place write gives a select write's values, the
compiled step aliases its cache input, and the engine drops the old
cache. (That the write stays local on a sharded cache is compiled for
a described v5e mesh in tests/test_chip_compile.py.)"""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import attention
from repro.models import model as M
from repro.models.config import AttnSpec
from repro.parallel.sharding import single_device_rules
from repro.serving.engine import DecodeEngine, Request

B, T, STEPS = 4, 32, 4


@pytest.fixture(scope="module")
def rules():
    return single_device_rules()


def _sliding(cfg, window=8):
    def fix(layer):
        return tuple(dataclasses.replace(s, sliding_window=window)
                     if isinstance(s, AttnSpec) else s for s in layer)
    return dataclasses.replace(cfg, pattern=tuple(fix(l) for l in cfg.pattern))


# (config, cache dtype): GQA, MHA, the int8 cache, a sliding window,
# recurrent layers beside a shared attention (and a recurrent tail),
# and cross-attention read from the stack
CASES = {
    "gqa": ("mistral-nemo-12b", jnp.float32),
    "mha": ("deepseek-7b", jnp.float32),
    "int8": ("mistral-nemo-12b", jnp.int8),
    "sliding": ("sliding", jnp.float32),
    "recurrent": ("zamba2-7b", jnp.float32),
    "cross": ("whisper-medium", jnp.float32),
}


def _config(name):
    if name == "sliding":
        return _sliding(get_config("mistral-nemo-12b", reduced=True))
    return get_config(name, reduced=True)


def _filled_cache(cfg, dtype, key):
    """A cache whose every position holds values, so each slot reads a
    prefix that differs by slot."""
    cache = M.init_cache(cfg, B, T, dtype=dtype)
    leaves, tree = jax.tree.flatten(cache)
    out = []
    for k, a in zip(jax.random.split(key, len(leaves)), leaves):
        if a.dtype == jnp.int8:
            out.append(jax.random.randint(k, a.shape, -127, 128, jnp.int8))
        elif a.shape[-1] == 1:          # int8 cache scales
            out.append(jax.random.uniform(k, a.shape, jnp.float32, 0.005,
                                          0.02).astype(a.dtype))
        else:
            out.append((0.3 * jax.random.normal(k, a.shape)).astype(a.dtype))
    return jax.tree.unflatten(tree, out)


def _serve(params, cache, step):
    """STEPS greedy steps over four slots: two decoding from different
    fill points, one at max_len - 2 that writes the last row and then
    sits finished at max_len - 1, and one parked (index and token held).
    Returns (tokens per step, logits per step, final cache)."""
    idx = np.array([3, 9, T - 2, 6], np.int32)
    active = np.array([True, True, True, False])
    tok = np.arange(1, B + 1, dtype=np.int32)
    toks, logits = [], []
    for _ in range(STEPS):
        cache, lg = step(params, token=jnp.asarray(tok[:, None]),
                         cache=cache, index=jnp.asarray(idx))
        lg = np.asarray(lg)
        tok = np.where(active, lg.argmax(-1).astype(np.int32), tok)
        idx = np.where(active, idx + 1, idx)
        active &= idx < T - 1
        toks.append(tok.copy())
        logits.append(lg)
    return toks, logits, cache


def _select_rows(stack, rows, layer, index):
    """The write as an elementwise select over the whole stack: what the
    scatter must equal, row past the end dropped included."""
    G, B, _, T, _ = stack.shape
    idx = jnp.broadcast_to(jnp.asarray(index), (B,))
    hit = ((jnp.arange(G) == layer)[:, None, None, None, None]
           & (jnp.arange(T)[None, :] == idx[:, None])[None, :, None, :, None])
    return jnp.where(hit, rows[None], stack)


@pytest.mark.parametrize("case", list(CASES))
def test_in_place_write_equals_select_write(case, rules, monkeypatch):
    name, dtype = CASES[case]
    cfg = _config(name)
    params, _ = M.init_params(jax.random.PRNGKey(0), cfg)
    scatter = attention._write_rows
    results = {}
    for write in (scatter, _select_rows):
        monkeypatch.setattr(attention, "_write_rows", write)
        step = jax.jit(functools.partial(M.decode_step, cfg=cfg, rules=rules,
                                         compute_dtype=jnp.float32))
        cache = _filled_cache(cfg, dtype, jax.random.PRNGKey(1))
        results[write] = _serve(params, cache, step)
    (tk_a, lg_a, c_a), (tk_b, lg_b, c_b) = results.values()
    np.testing.assert_array_equal(np.stack(tk_a), np.stack(tk_b))
    for a, b in zip(jax.tree.leaves(c_a), jax.tree.leaves(c_b)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    if dtype == jnp.int8:
        # the tolerance of tests/test_kv_int8.py's int8 decode checks
        np.testing.assert_allclose(np.stack(lg_a), np.stack(lg_b),
                                   rtol=5e-2, atol=5e-2)
    else:
        np.testing.assert_array_equal(np.stack(lg_a), np.stack(lg_b))


@pytest.fixture(scope="module")
def engine_setup(rules):
    cfg = get_config("mistral-nemo-12b", reduced=True)
    params, _ = M.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, rules, params


def _engine(engine_setup):
    cfg, rules, params = engine_setup
    eng = DecodeEngine(cfg, params, rules, max_slots=3, max_len=32)
    for i, n in enumerate((5, 9)):
        eng.admit(Request(rid=f"r{i}", prompt=np.arange(1, n + 1,
                                                        dtype=np.int32),
                          max_new=8))
    return eng


def test_compiled_step_aliases_the_whole_cache(engine_setup):
    eng = _engine(engine_setup)
    compiled = eng._decode.lower(
        eng.params, token=jnp.zeros((eng.max_slots, 1), jnp.int32),
        cache=eng.cache, index=jnp.zeros(eng.max_slots, jnp.int32)).compile()
    cache_bytes = sum(a.nbytes for a in jax.tree.leaves(eng.cache))
    assert compiled.memory_analysis().alias_size_in_bytes == cache_bytes


def test_step_consumes_the_old_cache(engine_setup):
    eng = _engine(engine_setup)
    before = jax.tree.leaves(eng.cache)
    eng.step()
    assert all(a.is_deleted() for a in before)
    assert not any(a.is_deleted() for a in jax.tree.leaves(eng.cache))


def test_a_step_that_hands_back_its_input_cache_raises(engine_setup):
    """A decode that returns the cache it was given (state left
    unchanged) cannot serve stale KV: that cache was donated, so the
    next step fails loudly."""
    eng = _engine(engine_setup)
    real = eng._decode

    def decode(params, *, token, cache, index):
        return cache, real(params, token=token, cache=cache,
                           index=index)[1]
    eng._decode = decode
    eng.step()
    # jax's dispatch or the runtime refuses the deleted buffers
    with pytest.raises((RuntimeError, ValueError), match="deleted"):
        eng.step()
