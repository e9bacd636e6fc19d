"""Jit'd wrapper for the fused ANN distance+top-k kernel."""
from __future__ import annotations

import functools

import jax

from .. import interpret_mode
from .kernel import ann_topk_fwd


def ann_topk(queries, corpus, *, k: int = 16, block_q: int = 128,
             tile: int = 512):
    return _topk(queries, corpus, k=k, block_q=block_q, tile=tile,
                 interpret=interpret_mode())


@functools.partial(jax.jit, static_argnames=("k", "block_q", "tile",
                                             "interpret"))
def _topk(queries, corpus, *, k, block_q, tile, interpret):
    return ann_topk_fwd(queries, corpus, k=k, block_q=block_q, tile=tile,
                        interpret=interpret)
