"""Single-token (decode) attention kernel over a paged/filled KV cache.

This is the IOPS-analog of the paper's fine-grained random reads: one new
query per sequence attends over a long cached context. Tiling: grid =
(batch, n_kv_blocks) with the kv axis sequential; every head of a batch
row is processed together (q is [H, hd] — small enough for VMEM at any
assigned config), so the kernel streams the cache exactly once per step.

The `length` operand masks the un-filled cache tail (per-batch fill
levels), supporting continuous batching where sequences fill at different
rates.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr,
                   acc_scr, *, scale: float, block_k: int, n_kv_blocks: int,
                   q_per_kv: int):
    ki = pl.program_id(1)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32)               # [H, hd]
    k = k_ref[0].astype(jnp.float32)               # [KV, bk, hd]
    v = v_ref[0].astype(jnp.float32)
    H, hd = q.shape
    KV = k.shape[0]
    # zero the un-filled tail: padded cache blocks may hold garbage and
    # 0 * garbage propagates NaN through the p @ v accumulation
    cols = ki * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (KV, block_k, hd), 1)
    live = cols < len_ref[0]
    k = jnp.where(live, k, 0.0)
    v = jnp.where(live, v, 0.0)
    qg = q.reshape(KV, q_per_kv, hd)
    # scores [KV, q_per_kv, bk]
    s = jax.lax.dot_general(
        qg, k, (((2,), (2,)), ((0,), (0,))),
        preferred_element_type=jnp.float32) * scale
    pos = ki * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (KV, q_per_kv, block_k), 2)
    valid = pos < len_ref[0]
    s = jnp.where(valid, s, NEG_INF)
    m_prev, l_prev = m_scr[...], l_scr[...]
    m_new = jnp.maximum(m_prev, s.max(axis=2))
    p = jnp.exp(s - m_new[..., None])
    corr = jnp.exp(m_prev - m_new)
    l_new = l_prev * corr + p.sum(axis=2)
    # acc [KV, q_per_kv, hd] += p @ v
    upd = jax.lax.dot_general(
        p, v, (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)
    acc_scr[...] = acc_scr[...] * corr[..., None] + upd
    m_scr[...] = m_new
    l_scr[...] = l_new

    @pl.when(ki == n_kv_blocks - 1)
    def _finish():
        out = acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)[..., None]
        o_ref[0] = out.reshape(H, hd).astype(o_ref.dtype)


def decode_attention_fwd(q, k, v, lengths, *, scale: float,
                         block_k: int = 512, interpret: bool):
    """q [B,H,hd]; k,v [B,KV,T,hd]; lengths [B] int32 -> o [B,H,hd]."""
    B, H, hd = q.shape
    KV, T = k.shape[1], k.shape[2]
    qr = H // KV
    block_k = min(block_k, T)
    n_k = pl.cdiv(T, block_k)

    kernel = functools.partial(
        _decode_kernel, scale=scale, block_k=block_k, n_kv_blocks=n_k,
        q_per_kv=qr)

    return pl.pallas_call(
        kernel,
        grid=(B, n_k),
        in_specs=[
            pl.BlockSpec((1,), lambda b, ki: (b,)),
            pl.BlockSpec((1, H, hd), lambda b, ki: (b, 0, 0)),
            pl.BlockSpec((1, KV, block_k, hd), lambda b, ki: (b, 0, ki, 0)),
            pl.BlockSpec((1, KV, block_k, hd), lambda b, ki: (b, 0, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, H, hd), lambda b, ki: (b, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((KV, qr), jnp.float32),
            pltpu.VMEM((KV, qr), jnp.float32),
            pltpu.VMEM((KV, qr, hd), jnp.float32),
        ],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
    )(lengths, q, k, v)
