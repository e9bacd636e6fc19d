"""Fused RMSNorm(+scale) kernel: one HBM read, one write per row block.

Rows (tokens) are tiled in blocks of `block_rows`; the feature dim stays
whole in VMEM (d_model <= 8192 for every assigned arch = 32KB/row in f32,
well inside the ~16MB VMEM budget at the default 128-row block).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _rmsnorm_kernel(x_ref, s_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    o_ref[...] = (x * jax.lax.rsqrt(var + eps)
                  * s_ref[...].astype(jnp.float32)).astype(o_ref.dtype)


def rmsnorm_fwd(x, scale, *, eps: float = 1e-6, block_rows: int = 128,
                interpret: bool):
    """x [N, D]; scale [D] -> [N, D]."""
    N, D = x.shape
    block_rows = min(block_rows, N)
    n_blocks = pl.cdiv(N, block_rows)
    return pl.pallas_call(
        functools.partial(_rmsnorm_kernel, eps=eps),
        grid=(n_blocks,),
        in_specs=[
            pl.BlockSpec((block_rows, D), lambda i: (i, 0)),
            pl.BlockSpec((D,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((block_rows, D), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((N, D), x.dtype),
        interpret=interpret,
    )(x, scale)
