"""Per-kernel shape/dtype sweeps against the pure-jnp oracles (interpret
mode on CPU), plus hypothesis property tests."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import reference_attention
from repro.kernels.decode_attention.ops import decode_attention
from repro.kernels.decode_attention.ref import reference_decode_attention
from repro.kernels.rmsnorm.ops import rmsnorm
from repro.kernels.rmsnorm.ref import reference_rmsnorm
from repro.kernels.ann_topk.ops import ann_topk
from repro.kernels.ann_topk.ref import reference_ann_topk
from repro.kernels.cuckoo_probe.ops import cuckoo_probe, hash_pair
from repro.kernels.cuckoo_probe.ref import reference_cuckoo_probe


def _rand(key, shape, dtype):
    x = jax.random.normal(key, shape, jnp.float32)
    return x.astype(dtype)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,H,KV,S,D", [
    (2, 4, 2, 128, 64),      # GQA
    (1, 8, 1, 256, 128),     # MQA
    (2, 4, 4, 200, 80),      # MHA, ragged seq, odd head_dim
    (1, 2, 2, 384, 112),     # zamba2 head_dim
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_sweep(B, H, KV, S, D, dtype, causal):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = _rand(ks[0], (B, H, S, D), dtype)
    k = _rand(ks[1], (B, KV, S, D), dtype)
    v = _rand(ks[2], (B, KV, S, D), dtype)
    o = flash_attention(q, k, v, causal, None)
    r = reference_attention(q, k, v, causal=causal, scale=1 / np.sqrt(D))
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(
        np.asarray(o, np.float32), np.asarray(r, np.float32),
        atol=tol, rtol=tol)


def test_flash_attention_grad_matches_reference():
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = _rand(ks[0], (1, 4, 64, 64), jnp.float32)
    k = _rand(ks[1], (1, 2, 64, 64), jnp.float32)
    v = _rand(ks[2], (1, 2, 64, 64), jnp.float32)

    def f_kernel(q, k, v):
        return jnp.sum(flash_attention(q, k, v, True, None) ** 2)

    def f_ref(q, k, v):
        return jnp.sum(reference_attention(
            q, k, v, causal=True, scale=1 / 8.0) ** 2)

    gk = jax.grad(f_kernel, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gk, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-4, rtol=1e-4)


# ---------------------------------------------------------------------------
# decode attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,H,KV,T,D", [
    (4, 8, 2, 1024, 64),
    (2, 8, 8, 600, 128),     # non-divisible T (padded tail)
    (3, 4, 1, 512, 128),
    (1, 16, 16, 96, 64),     # T < block_k
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention_sweep(B, H, KV, T, D, dtype):
    ks = jax.random.split(jax.random.PRNGKey(1), 4)
    q = _rand(ks[0], (B, H, D), dtype)
    k = _rand(ks[1], (B, KV, T, D), dtype)
    v = _rand(ks[2], (B, KV, T, D), dtype)
    lens = jax.random.randint(ks[3], (B,), 1, T + 1)
    o = decode_attention(q, k, v, lens)
    r = reference_decode_attention(q, k, v, lens, scale=1 / np.sqrt(D))
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(
        np.asarray(o, np.float32), np.asarray(r, np.float32),
        atol=tol, rtol=tol)


# ---------------------------------------------------------------------------
# rmsnorm
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("shape", [(64, 256), (3, 100, 512), (1, 8, 2048)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rmsnorm_sweep(shape, dtype):
    x = _rand(jax.random.PRNGKey(2), shape, dtype)
    s = jax.random.normal(jax.random.PRNGKey(3), (shape[-1],), jnp.float32)
    o = rmsnorm(x, s)
    r = reference_rmsnorm(x, s)
    tol = 1e-5 if dtype == jnp.float32 else 4e-2
    np.testing.assert_allclose(
        np.asarray(o, np.float32), np.asarray(r, np.float32),
        atol=tol, rtol=tol)


@settings(max_examples=20, deadline=None)
@given(n=st.integers(1, 64), d=st.sampled_from([8, 128, 384]),
       scale=st.floats(0.1, 10.0))
def test_rmsnorm_output_rms_is_scale(n, d, scale):
    """Property: with unit scale vector * c, output RMS ~= c."""
    x = jax.random.normal(jax.random.PRNGKey(n), (n, d), jnp.float32) \
        * scale
    s = jnp.ones((d,), jnp.float32)
    o = np.asarray(rmsnorm(x, s))
    rms = np.sqrt((o ** 2).mean(axis=-1))
    np.testing.assert_allclose(rms, 1.0, atol=1e-2)


# ---------------------------------------------------------------------------
# ann topk
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("Q,N,D,k,tile", [
    (64, 1000, 64, 8, 256),
    (100, 2000, 128, 16, 512),
    (16, 300, 32, 4, 128),    # ragged corpus tail
    (200, 1500, 64, 8, 256),  # partial last query block
])
def test_ann_topk_sweep(Q, N, D, k, tile):
    qs = jax.random.normal(jax.random.PRNGKey(5), (Q, D), jnp.float32)
    corpus = jax.random.normal(jax.random.PRNGKey(6), (N, D), jnp.float32)
    d, i = ann_topk(qs, corpus, k=k, tile=tile)
    rd, ri = reference_ann_topk(qs, corpus, k=k)
    np.testing.assert_allclose(np.sort(np.asarray(d), axis=1),
                               np.sort(np.asarray(rd), axis=1), atol=1e-3)
    assert (np.sort(np.asarray(i), axis=1)
            == np.sort(np.asarray(ri), axis=1)).mean() > 0.99


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 2**16))
def test_ann_topk_self_retrieval(seed):
    """Property: a corpus vector queries itself as its own top-1."""
    corpus = jax.random.normal(jax.random.PRNGKey(seed), (257, 32),
                               jnp.float32)
    d, i = ann_topk(corpus[:32], corpus, k=1, tile=64)
    assert (np.asarray(i)[:, 0] == np.arange(32)).all()


# ---------------------------------------------------------------------------
# cuckoo probe
# ---------------------------------------------------------------------------

def _build_table(nb, slots, n_items, seed=0):
    rng = np.random.default_rng(seed)
    bk = np.zeros((nb, slots), np.int32)
    bv = np.zeros((nb, slots), np.int32)
    keys = rng.choice(np.arange(1, 10**6), size=n_items,
                      replace=False).astype(np.int32)
    b1, b2 = (np.asarray(h) for h in hash_pair(jnp.asarray(keys), nb))
    stored = []
    for kk, x1, x2 in zip(keys, b1, b2):
        for b in (x1, x2):
            free = np.where(bk[b] == 0)[0]
            if len(free):
                bk[b, free[0]] = kk
                bv[b, free[0]] = int(kk) % 9973
                stored.append(kk)
                break
    return bk, bv, np.array(stored, np.int32)


@pytest.mark.parametrize("nb,slots,n", [(128, 8, 400), (512, 4, 800)])
def test_cuckoo_probe_sweep(nb, slots, n):
    bk, bv, stored = _build_table(nb, slots, n)
    rng = np.random.default_rng(1)
    miss = rng.integers(2 * 10**6, 3 * 10**6, 64).astype(np.int32)
    probe = np.concatenate([stored[:128], miss])
    f, v = cuckoo_probe(jnp.asarray(probe), jnp.asarray(bk),
                        jnp.asarray(bv))
    rf, rv = reference_cuckoo_probe(
        jnp.asarray(probe), *hash_pair(jnp.asarray(probe), nb),
        jnp.asarray(bk), jnp.asarray(bv))
    assert (np.asarray(f) == np.asarray(rf)).all()
    assert (np.asarray(v) == np.asarray(rv)).all()
    n_stored = min(128, len(stored))
    assert np.asarray(f)[:n_stored].all()
    assert not np.asarray(f)[len(probe) - 64:].any()


def test_interpret_mode_follows_the_backend():
    """Kernels run interpreted only on the CPU backend; anywhere else a
    wrapper compiles its kernel."""
    from repro.kernels import interpret_mode
    assert interpret_mode() == (jax.default_backend() == "cpu")
