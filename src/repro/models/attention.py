"""GQA/MQA/MHA attention with RoPE / M-RoPE, KV cache, chunked long-context
path, and optional cross-attention.

KV caches are laid out [B, n_kv, max_len, head_dim] (kv-heads before seq) so
the sharding rules can claim the "model" axis for kv-heads when divisible
and fall back to sharding the sequence dimension otherwise (MQA/GQA with
few kv heads at TP=16).

The quadratic score matrix is never materialized for long sequences: when
S * kv_len exceeds `ctx.attn_chunk`^2-ish budgets the kv axis is processed
in blocks with an online-softmax accumulator (flash-attention recurrence,
pure jnp — the Pallas kernel in repro.kernels.flash_attention implements
the same recurrence for TPU and is validated against this path).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .config import AttnSpec, ModelConfig
from .layers import (Ctx, apply_mrope, apply_rope, dense_init,
                     rms_norm_heads)

NEG_INF = -0.7 * float(np.finfo(np.float32).max)


# ---------------------------------------------------------------------------
# Params
# ---------------------------------------------------------------------------

def init(key, cfg: ModelConfig, spec: AttnSpec):
    d = cfg.d_model
    ks = jax.random.split(key, 4)
    params = {
        "wq": dense_init(ks[0], (d, spec.n_heads, spec.head_dim), fan_in=d),
        "wk": dense_init(ks[1], (d, spec.n_kv, spec.head_dim), fan_in=d),
        "wv": dense_init(ks[2], (d, spec.n_kv, spec.head_dim), fan_in=d),
        "wo": dense_init(ks[3], (spec.n_heads, spec.head_dim, d),
                         fan_in=spec.n_heads * spec.head_dim),
    }
    if spec.qk_norm:
        params["q_scale"] = jnp.ones((spec.head_dim,), jnp.float32)
        params["k_scale"] = jnp.ones((spec.head_dim,), jnp.float32)
    return params, logical(cfg, spec)


def logical(cfg: ModelConfig, spec: AttnSpec):
    out = {
        "wq": ("embed", "heads", "head_dim"),
        "wk": ("embed", "kv_heads", "head_dim"),
        "wv": ("embed", "kv_heads", "head_dim"),
        "wo": ("heads", "head_dim", "embed"),
    }
    if spec.qk_norm:
        out["q_scale"] = ("head_dim",)
        out["k_scale"] = ("head_dim",)
    return out


def init_cache(cfg: ModelConfig, spec: AttnSpec, batch: int, max_len: int,
               dtype=jnp.bfloat16, enc_len: int = 0):
    """Abstract/zero cache for one attention sublayer.

    dtype=jnp.int8 selects the quantized cache: per-(position, kv-head)
    symmetric int8 with a bf16 scale — halves decode's dominant HBM term
    (cache reads) at ~1e-2 relative error on attention outputs."""
    kv_len = enc_len if spec.cross else max_len
    shape = (batch, spec.n_kv, kv_len, spec.head_dim)
    cache = {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}
    if dtype == jnp.int8:
        sshape = shape[:-1] + (1,)
        cache["k_scale"] = jnp.zeros(sshape, jnp.bfloat16)
        cache["v_scale"] = jnp.zeros(sshape, jnp.bfloat16)
    return cache


def cache_logical(spec: AttnSpec, quantized: bool = False):
    names = ("cache_batch", "cache_kv", "cache_seq", "head_dim")
    out = {"k": names, "v": names}
    if quantized:
        out["k_scale"] = names
        out["v_scale"] = names
    return out


def _quantize_kv(x):
    """x [..., hd] -> (int8 values, bf16 per-row scale)."""
    scale = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1,
                    keepdims=True) / 127.0
    scale = jnp.maximum(scale, 1e-8)
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale), -127, 127)
    return q.astype(jnp.int8), scale.astype(jnp.bfloat16)


def _read_cache(cache, dt):
    """Dequantize (if int8) and cast the cache for attention compute."""
    if cache["k"].dtype == jnp.int8:
        k = (cache["k"].astype(jnp.float32)
             * cache["k_scale"].astype(jnp.float32)).astype(dt)
        v = (cache["v"].astype(jnp.float32)
             * cache["v_scale"].astype(jnp.float32)).astype(dt)
        return k, v
    return cache["k"], cache["v"]


def _layer_of(cache, layer):
    """The [B, ...] slice of group `layer` of a stacked [G, B, ...] cache;
    the cache itself when `layer` is None."""
    if layer is None:
        return cache
    return {key: jax.lax.dynamic_index_in_dim(c, layer, 0, keepdims=False)
            for key, c in cache.items()}


def _write_rows(stack, rows, layer, index):
    """Scatter each slot's new row into a stacked cache, in place:
    stack [G, B, KV, T, hd]; rows [B, KV, 1, hd]; row (b, g) lands at
    (layer, b, g, index[b]). An index past T drops its row, as the
    select's mask does. One hd-wide row per (slot, kv head): scattering
    [KV, hd] windows makes the TPU's layout pass store the stack with
    the sequence inside the kv heads, which copies it whole."""
    B, KV = rows.shape[:2]
    idx = jnp.broadcast_to(jnp.asarray(index), (B,))
    return stack.at[layer, jnp.arange(B)[:, None], jnp.arange(KV)[None, :],
                    idx[:, None]].set(
        rows[:, :, 0], indices_are_sorted=True, unique_indices=True)


# ---------------------------------------------------------------------------
# Core scaled-dot-product (GQA, no kv repeat materialization)
# ---------------------------------------------------------------------------

def _sdpa_full(q, k, v, mask, scale, softcap=0.0):
    """q [B,S,KV,QR,hd]; k,v [B,KV,T,hd]; mask [B?,S,T] bool or None."""
    scores = jnp.einsum("bsgqh,bgth->bgqst", q, k,
                        preferred_element_type=jnp.float32) * scale
    if softcap > 0.0:
        scores = jnp.tanh(scores / softcap) * softcap
    if mask is not None:
        scores = jnp.where(mask[:, None, None, :, :], scores, NEG_INF)
    w = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bgqst,bgth->bsgqh", w.astype(v.dtype), v)
    return out


def _sdpa_chunked(q, k, v, q_pos, kv_pos, scale, causal, chunk,
                  softcap=0.0, window=0):
    """Online-softmax over kv blocks. q [B,S,KV,QR,hd]; k,v [B,KV,T,hd];
    q_pos [B,S]; kv_pos [T]. Memory O(S * chunk) instead of O(S * T)."""
    B, S, KV, QR, H = q.shape
    T = k.shape[2]
    n_blocks = -(-T // chunk)
    pad = n_blocks * chunk - T
    if pad:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
        kv_pos = jnp.pad(kv_pos, (0, pad), constant_values=2**30)
    kb = k.reshape(B, KV, n_blocks, chunk, H).transpose(2, 0, 1, 3, 4)
    vb = v.reshape(B, KV, n_blocks, chunk, H).transpose(2, 0, 1, 3, 4)
    pb = kv_pos.reshape(n_blocks, chunk)

    qf = q.astype(jnp.float32)

    def body(carry, blk):
        m, l, acc = carry
        kc, vc, pc = blk
        s = jnp.einsum("bsgqh,bgth->bgqst", qf, kc.astype(jnp.float32)) * scale
        if softcap > 0.0:
            s = jnp.tanh(s / softcap) * softcap
        valid = jnp.broadcast_to(pc[None, None, :] < 2**30, (B, S, chunk))
        if causal:
            ok = q_pos[:, :, None] >= pc[None, None, :]
            if window:
                ok &= q_pos[:, :, None] - pc[None, None, :] < window
            valid = valid & ok
        # valid [B,S,chunk] -> broadcast over (KV, QR): s is [B,KV,QR,S,chunk]
        s = jnp.where(valid[:, None, None, :, :], s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bgqst,bgth->bgqsh", p, vc.astype(jnp.float32))
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((B, KV, QR, S), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, KV, QR, S), jnp.float32)
    a0 = jnp.zeros((B, KV, QR, S, H), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, a0), (kb, vb, pb))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.transpose(0, 3, 1, 2, 4).astype(q.dtype)   # [B,S,KV,QR,hd]


# ---------------------------------------------------------------------------
# Sublayer apply
# ---------------------------------------------------------------------------

def apply(params, x, spec: AttnSpec, cfg: ModelConfig, ctx: Ctx,
          cache=None) -> Tuple[jax.Array, Optional[dict]]:
    """x [B,S,D] (already normed). Returns (attn_out [B,S,D], new_cache)."""
    B, S, D = x.shape
    dt = ctx.compute_dtype
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"].astype(dt))
    quant = cache is not None and cache["k"].dtype == jnp.int8
    if spec.cross:
        src = ctx.enc_out
        if cache is not None and ctx.mode == "decode":
            # projected at prefill
            k, v = _read_cache(_layer_of(cache, ctx.layer), dt)
            new_cache = cache
        else:
            k = jnp.einsum("btd,dgk->bgtk", src, params["wk"].astype(dt))
            v = jnp.einsum("btd,dgk->bgtk", src, params["wv"].astype(dt))
            new_cache = None
            if cache is not None:
                if quant:
                    qk, sk = _quantize_kv(k)
                    qv, sv = _quantize_kv(v)
                    new_cache = {"k": qk, "v": qv, "k_scale": sk,
                                 "v_scale": sv}
                else:
                    new_cache = {"k": k, "v": v}
        kv_pos = jnp.arange(k.shape[2])
        q_pos = None
        causal = False
    else:
        k = jnp.einsum("bsd,dgk->bgsk", x, params["wk"].astype(dt))
        v = jnp.einsum("bsd,dgk->bgsk", x, params["wv"].astype(dt))
        if spec.qk_norm:
            q = rms_norm_heads(q, params["q_scale"], cfg.norm_eps)
            k = rms_norm_heads(
                k.transpose(0, 2, 1, 3), params["k_scale"],
                cfg.norm_eps).transpose(0, 2, 1, 3)
        pos = ctx.positions
        if spec.rope == "rope":
            q = apply_rope(q, pos, cfg.rope_theta)
            k = apply_rope(k.transpose(0, 2, 1, 3), pos,
                           cfg.rope_theta).transpose(0, 2, 1, 3)
        elif spec.rope == "mrope":
            q = apply_mrope(q, pos, cfg.rope_theta, spec.mrope_sections)
            k = apply_mrope(k.transpose(0, 2, 1, 3), pos, cfg.rope_theta,
                            spec.mrope_sections).transpose(0, 2, 1, 3)
        q_pos = pos if pos.ndim == 2 else pos[0]

        if cache is not None:
            with jax.named_scope("kv_update"):
                if quant:
                    k_w, sk_w = _quantize_kv(k)
                    v_w, sv_w = _quantize_kv(v)
                    writes = {"k": k_w, "v": v_w, "k_scale": sk_w,
                              "v_scale": sv_w}
                else:
                    writes = {"k": k.astype(cache["k"].dtype),
                              "v": v.astype(cache["v"].dtype)}
                if ctx.mode == "prefill":
                    # static offset 0: plain slice-update keeps sharding
                    new_cache = {
                        key: jax.lax.dynamic_update_slice(
                            cache[key], w, (0, 0, 0, 0))
                        for key, w in writes.items()}
                elif ctx.layer is not None:
                    # decode in place: `cache` is this sublayer's whole
                    # [G, B, ...] stack, carried through the layer loop
                    # (and donated by the engine), so only the new rows
                    # are written
                    new_cache = {key: _write_rows(cache[key], w, ctx.layer,
                                                  ctx.cache_index)
                                 for key, w in writes.items()}
                else:
                    # decode of a tail cache (outside the layer loop):
                    # select-based write — a dynamic-index
                    # dynamic_update_slice on the (possibly seq-sharded) cache
                    # would force GSPMD to gather the whole cache per step;
                    # where(iota==idx, ...) is elementwise and stays sharded.
                    # cache_index may be scalar or per-slot [B] (continuous
                    # batching).
                    iota = jnp.arange(cache["k"].shape[2])[None, None, :, None]
                    idx_ = jnp.asarray(ctx.cache_index)
                    if idx_.ndim == 1:
                        idx_ = idx_[:, None, None, None]
                    sel = iota == idx_
                    new_cache = {key: jnp.where(sel, w, cache[key])
                                 for key, w in writes.items()}
                logi = cache_logical(spec, quantized=quant)
                lead = () if ctx.layer is None else ("layers",)
                new_cache = {key: ctx.rules.constrain(c, *lead, *logi[key])
                             for key, c in new_cache.items()}
            k, v = _read_cache(_layer_of(new_cache, ctx.layer), dt)
            kv_pos = jnp.arange(k.shape[2])
        else:
            new_cache = None
            kv_pos = q_pos[0] if q_pos.ndim == 2 else q_pos
        causal = spec.causal

    # reshape q to grouped layout [B,S,KV,QR,hd]
    QR = spec.n_heads // spec.n_kv
    q = q.reshape(B, S, spec.n_kv, QR, spec.head_dim)
    # kv-heads claim the TP axis when divisible; otherwise the query-repeat
    # dim takes it (a fully-specified constraint with None here would FORCE
    # replication and materialize unsharded score tensors)
    q = ctx.rules.constrain(q, "batch", None, "act_kv", "act_qr", None)
    scale = 1.0 / np.sqrt(spec.head_dim)
    T = k.shape[2]

    use_chunked = (not ctx.cost_exact) and S > 1 and S * T > 1024 * 1024 \
        and not spec.cross
    if use_chunked:
        out = _sdpa_chunked(q, k, v, q_pos, kv_pos, scale, causal,
                            ctx.attn_chunk, spec.logit_softcap,
                            spec.sliding_window)
    else:
        mask = None
        if causal:
            if S == 1 and ctx.cache_index is not None:
                # decode: attend to the filled prefix (incl. current slot)
                cur = jnp.asarray(ctx.cache_index)
                if cur.ndim == 1:
                    cur = cur[:, None, None]
                mask = jnp.broadcast_to(
                    kv_pos[None, None, :] <= cur, (B, 1, T))
                if spec.sliding_window:
                    mask &= jnp.broadcast_to(
                        cur - kv_pos[None, None, :] < spec.sliding_window,
                        (B, 1, T))
            else:
                mask = (q_pos[:, :, None] >= kv_pos[None, None, :])
                if spec.sliding_window:
                    mask &= (q_pos[:, :, None] - kv_pos[None, None, :]
                             < spec.sliding_window)
        out = _sdpa_full(q, k, v, mask, scale, spec.logit_softcap)

    out = out.reshape(B, S, spec.n_heads, spec.head_dim)
    y = jnp.einsum("bshk,hkd->bsd", out, params["wo"].astype(dt))
    return y, new_cache
