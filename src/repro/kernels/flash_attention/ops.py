"""Jit'd public wrapper for the flash-attention kernel.

Handles head_dim padding to the 128-lane boundary, dtype plumbing, and a
custom_vjp whose backward pass recomputes through the jnp oracle (the
forward kernel is the serving hot spot; training backward goes through
XLA — documented trade-off).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .. import interpret_mode
from .kernel import flash_attention_fwd
from .ref import reference_attention


def _pad_head(x, target):
    d = x.shape[-1]
    if d == target:
        return x
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, target - d)])


def flash_attention(q, k, v, causal: bool = True, scale: float = None):
    """q [B,H,S,hd]; k,v [B,KV,T,hd] -> [B,H,S,hd]."""
    return _flash(q, k, v, causal, scale, interpret_mode())


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _flash(q, k, v, causal, scale, interpret):
    return _fwd_impl(q, k, v, causal, scale, interpret)


def _fwd_impl(q, k, v, causal, scale, interpret):
    D = q.shape[-1]
    scale = scale if scale is not None else 1.0 / (D ** 0.5)
    Dp = max(128, -(-D // 128) * 128) if not interpret else D
    qp, kp, vp = (_pad_head(t, Dp) for t in (q, k, v))
    o = flash_attention_fwd(qp, kp, vp, causal=causal, scale=scale,
                            interpret=interpret)
    return o[..., :D]


def _fwd_vjp(q, k, v, causal, scale, interpret):
    return _fwd_impl(q, k, v, causal, scale, interpret), (q, k, v)


def _bwd_vjp(causal, scale, interpret, res, g):
    q, k, v = res
    D = q.shape[-1]
    s = scale if scale is not None else 1.0 / (D ** 0.5)
    _, vjp = jax.vjp(
        lambda q_, k_, v_: reference_attention(q_, k_, v_, causal=causal,
                                               scale=s), q, k, v)
    return vjp(g)


_flash.defvjp(_fwd_vjp, _bwd_vjp)
