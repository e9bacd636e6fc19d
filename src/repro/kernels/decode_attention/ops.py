"""Jit'd wrapper for the decode-attention kernel."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .. import interpret_mode
from .kernel import decode_attention_fwd


def decode_attention(q, k, v, lengths, *, scale: float = None,
                     block_k: int = 512):
    """One-token attention over a filled KV cache.

    q [B,H,hd]; k,v [B,KV,T,hd]; lengths [B] int32."""
    return _decode(q, k, v, lengths, scale=scale, block_k=block_k,
                   interpret=interpret_mode())


@functools.partial(jax.jit, static_argnames=("scale", "block_k",
                                             "interpret"))
def _decode(q, k, v, lengths, *, scale, block_k, interpret):
    hd = q.shape[-1]
    s = scale if scale is not None else 1.0 / (hd ** 0.5)
    return decode_attention_fwd(q, k, v, lengths.astype(jnp.int32),
                                scale=s, block_k=block_k,
                                interpret=interpret)
