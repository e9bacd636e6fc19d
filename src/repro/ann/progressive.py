"""Two-stage progressive SSD-resident ANN search (paper §VII-B, Fig. 9).

Stage 1: scan *reduced* vectors (512B-class rows) with the fused
distance+top-M Pallas kernel — predominantly small-block reads, the
IOPS-friendly regime Storage-Next unlocks.
Stage 2: re-rank the small promoted candidate set on *full* vectors
(2-8KB rows) — the bandwidth-bound tail, amortized by the >90% rejection
rate of stage 1 (Gao et al.).

`search` measures recall against exact brute force; the paper's >98%
recall claim is validated on the MRL-like corpus in tests/benchmarks.
"""
from __future__ import annotations

import dataclasses
from typing import Tuple

import jax.numpy as jnp
import numpy as np

from ..kernels.ann_topk.ops import ann_topk


@dataclasses.dataclass
class SearchStats:
    queries: int = 0
    stage1_reads: int = 0            # reduced-vector row reads (512B-class)
    stage2_reads: int = 0            # full-vector row reads (KB-class)


def exact_topk(queries: np.ndarray, corpus: np.ndarray, k: int):
    d = (np.sum(corpus ** 2, 1)[None, :]
         - 2.0 * queries @ corpus.T)
    # the k nearest in O(N), then ordered by distance among themselves
    part = np.argpartition(d, k - 1, axis=1)[:, :k]
    order = np.argsort(np.take_along_axis(d, part, axis=1), axis=1)
    return np.take_along_axis(part, order, axis=1)


def search(queries: np.ndarray, reduced: np.ndarray, full: np.ndarray,
           k: int = 10, promote: int = 64, stats: SearchStats = None,
           use_kernel: bool = True) -> Tuple[np.ndarray, SearchStats]:
    """Two-stage search. Returns (ids [Q, k], stats)."""
    stats = stats or SearchStats()
    Q = len(queries)
    d_red = reduced.shape[1]
    # stage 1: top-`promote` on reduced vectors
    if use_kernel:
        _, cand = ann_topk(jnp.asarray(queries[:, :d_red]),
                           jnp.asarray(reduced), k=promote,
                           tile=min(512, len(reduced)))
        cand = np.asarray(cand)
    else:
        cand = exact_topk(queries[:, :d_red], reduced, promote)
    stats.queries += Q
    stats.stage1_reads += Q * len(reduced)      # streamed scan rows
    # stage 2: exact re-rank of the promoted set on full vectors
    out = np.empty((Q, k), np.int64)
    gather = full[cand]                          # [Q, promote, D]
    stats.stage2_reads += Q * promote
    d2 = np.sum(gather ** 2, -1) - 2.0 * np.einsum(
        "qd,qpd->qp", queries, gather)
    order = np.argsort(d2, axis=1)[:, :k]
    out = np.take_along_axis(cand, order, axis=1)
    return out, stats


def recall_at_k(pred: np.ndarray, truth: np.ndarray) -> float:
    hits = 0
    for p, t in zip(pred, truth):
        hits += len(set(p.tolist()) & set(t.tolist()))
    return hits / truth.size
