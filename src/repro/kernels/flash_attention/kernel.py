"""Flash-attention forward kernel (causal GQA) for TPU.

Tiling: grid = (batch, q_heads, n_q_blocks, n_kv_blocks) with the kv axis
innermost and *sequential*; VMEM scratch carries the online-softmax state
(m, l, acc) across kv iterations, so the [S, T] score matrix never exists
in HBM. GQA is handled in the BlockSpec index maps (kv blocks are indexed
by h // q_per_kv), so no repeated-KV materialization either.

Block sizes default to (128, 512) — multiples of the 128-lane MXU tiling;
head_dim is padded to 128 by ops.py when needed (zamba2's hd=112).
Validated in interpret mode against ref.reference_attention.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _flash_fwd_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                      scale: float, causal: bool, block_q: int,
                      block_k: int, n_kv_blocks: int, seq_k: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_idx = qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    k_idx = ki * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)

    run = True
    if causal:
        # whole block above the diagonal contributes nothing
        run = (ki * block_k) <= (qi * block_q + block_q - 1)

    @pl.when(run if causal else True)
    def _step():
        q = q_ref[0, 0].astype(jnp.float32)            # [bq, hd]
        k = k_ref[0, 0].astype(jnp.float32)            # [bk, hd]
        v = v_ref[0, 0].astype(jnp.float32)            # [bk, hd]
        # zero padded tail rows (0 * garbage would propagate NaN via p@v)
        rows = ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, k.shape, 0)
        k = jnp.where(rows < seq_k, k, 0.0)
        v = jnp.where(rows < seq_k, v, 0.0)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # [bq, bk]
        mask = k_idx < seq_k
        if causal:
            mask &= q_idx >= k_idx
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_scr[...]
        l_prev = l_scr[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_new = l_prev * corr + p.sum(axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new
        l_scr[...] = l_new

    @pl.when(ki == n_kv_blocks - 1)
    def _finish():
        o_ref[0, 0] = (acc_scr[...]
                       / jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


def flash_attention_fwd(q, k, v, *, causal: bool = True, scale: float,
                        block_q: int = 128, block_k: int = 512,
                        interpret: bool):
    """q [B,H,S,hd]; k,v [B,KV,T,hd] -> o [B,H,S,hd]."""
    B, H, S, D = q.shape
    KV, T = k.shape[1], k.shape[2]
    qr = H // KV
    block_q = min(block_q, S)
    block_k = min(block_k, T)
    n_q = pl.cdiv(S, block_q)
    n_k = pl.cdiv(T, block_k)

    kernel = functools.partial(
        _flash_fwd_kernel, scale=scale, causal=causal, block_q=block_q,
        block_k=block_k, n_kv_blocks=n_k, seq_k=T)

    return pl.pallas_call(
        kernel,
        grid=(B, H, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D),
                         lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, qi, ki: (b, h // qr, ki, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, qi, ki: (b, h // qr, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, D),
                               lambda b, h, qi, ki: (b, h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, S, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, D), jnp.float32),
        ],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
    )(q, k, v)
