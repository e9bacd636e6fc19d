"""Jit'd wrapper for the fused RMSNorm kernel (arbitrary leading dims)."""
from __future__ import annotations

import functools

import jax

from .. import interpret_mode
from .kernel import rmsnorm_fwd


def rmsnorm(x, scale, *, eps: float = 1e-6, block_rows: int = 128):
    return _rmsnorm(x, scale, eps=eps, block_rows=block_rows,
                    interpret=interpret_mode())


@functools.partial(jax.jit, static_argnames=("eps", "block_rows",
                                             "interpret"))
def _rmsnorm(x, scale, *, eps, block_rows, interpret):
    shape = x.shape
    y = rmsnorm_fwd(x.reshape(-1, shape[-1]), scale, eps=eps,
                    block_rows=block_rows, interpret=interpret)
    return y.reshape(shape)
