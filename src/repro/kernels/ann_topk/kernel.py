"""Fused L2-distance + running top-k kernel (stage 1 of the paper's
two-stage progressive ANN search, §VII-B).

Grid = (n_query_blocks, n_corpus_tiles) with the corpus axis sequential.
Each step computes the [bq, tile] squared-L2 distances to one corpus tile
entirely in VMEM (matmul on the MXU + norm terms) and folds them into a
running top-k scratch via K rounds of masked arg-min extraction — the full
[Q, N] distance matrix never touches HBM, which is the point: at
N = 8B vectors (the paper's corpus) that matrix is unmaterializable.

K is small (<= 64). The extraction rounds run in a loop (bounded VMEM
whatever K), and a tile whose nearest point loses to every row's
current k-th best skips the merge — after the first tiles most do.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


BIG = 1e30


def _ann_kernel(q_ref, c_ref, od_ref, oi_ref, d_scr, i_scr, *, k: int,
                tile: int, n_tiles: int, n_corpus: int):
    ti = pl.program_id(1)

    @pl.when(ti == 0)
    def _init():
        d_scr[...] = jnp.full_like(d_scr, BIG)
        i_scr[...] = jnp.full_like(i_scr, -1)

    q = q_ref[...].astype(jnp.float32)              # [bq, D]
    c = c_ref[...].astype(jnp.float32)              # [tile, D]
    # squared L2 = |q|^2 - 2 q.c + |c|^2 ; |q|^2 is rank-constant, dropped.
    # |c|^2 comes off the MXU as a lane-major row: a lane reduction of
    # c*c would come out sublane-major and its transpose to a [1, tile]
    # row costs more VMEM than the kernel may use
    def nt(a, b):                                    # a @ b.T
        return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                                   precision=jax.lax.Precision.HIGHEST,
                                   preferred_element_type=jnp.float32)
    c_sq = nt(jnp.ones((8, c.shape[1]), jnp.float32), c * c)[:1]
    d = c_sq - 2.0 * nt(q, c)                        # [bq, tile]
    col_t = jax.lax.broadcasted_iota(jnp.int32, d.shape, 1)
    d = jnp.where(ti * tile + col_t < n_corpus, d, BIG)

    # a tile enters the running top-k only where it beats a row's k-th
    # best (the scratch is kept sorted ascending); decided row by row,
    # so the undefined rows of a partial last query block cannot veto
    beats = jnp.min(d, axis=1, keepdims=True) < d_scr[:, k - 1:]
    @pl.when(jnp.max(beats.astype(jnp.int32)) > 0)
    def _merge():
        run_d, run_i = d_scr[...], i_scr[...]
        col_r = jax.lax.broadcasted_iota(jnp.int32, run_d.shape, 1)

        # k rounds of masked arg-min over (running set, tile): each
        # round moves the smaller of the two minima into output column
        # r; ties keep the running (lower-id) entry, as a stable merge
        def round_(r, carry):
            dt, dr, out_d, out_i = carry
            mt = jnp.min(dt, axis=1, keepdims=True)          # [bq, 1]
            mr = jnp.min(dr, axis=1, keepdims=True)
            pt = jnp.min(jnp.where(dt == mt, col_t, tile), axis=1,
                         keepdims=True)                      # first arg-min
            pr = jnp.min(jnp.where(dr == mr, col_r, k), axis=1,
                         keepdims=True)
            take_t = mt < mr
            idr = jnp.sum(jnp.where(col_r == pr, run_i, 0), axis=1,
                          keepdims=True)
            best_i = jnp.where(take_t, ti * tile + pt, idr)
            dt = jnp.where(take_t & (col_t == pt), BIG, dt)
            dr = jnp.where(~take_t & (col_r == pr), BIG, dr)
            here = col_r == r
            out_d = jnp.where(here, jnp.minimum(mt, mr), out_d)
            out_i = jnp.where(here, best_i, out_i)
            return dt, dr, out_d, out_i

        _, _, out_d, out_i = jax.lax.fori_loop(
            0, k, round_, (d, run_d, run_d, run_i))
        d_scr[...] = out_d
        i_scr[...] = out_i

    @pl.when(ti == n_tiles - 1)
    def _finish():
        od_ref[...] = d_scr[...]
        oi_ref[...] = i_scr[...]


def ann_topk_fwd(queries, corpus, *, k: int = 16, block_q: int = 128,
                 tile: int = 512, interpret: bool):
    """queries [Q, D]; corpus [N, D] -> (dists [Q, k], ids [Q, k]).

    Distances omit the constant |q|^2 term (rank-preserving)."""
    Q, D = queries.shape
    N = corpus.shape[0]
    block_q = min(block_q, Q)
    tile = min(tile, N)
    nq = pl.cdiv(Q, block_q)
    nt = pl.cdiv(N, tile)
    kern = functools.partial(_ann_kernel, k=k, tile=tile, n_tiles=nt,
                             n_corpus=N)
    return pl.pallas_call(
        kern,
        grid=(nq, nt),
        in_specs=[
            pl.BlockSpec((block_q, D), lambda qi, ti: (qi, 0)),
            pl.BlockSpec((tile, D), lambda qi, ti: (ti, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_q, k), lambda qi, ti: (qi, 0)),
            pl.BlockSpec((block_q, k), lambda qi, ti: (qi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Q, k), jnp.float32),
            jax.ShapeDtypeStruct((Q, k), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, k), jnp.float32),
            pltpu.VMEM((block_q, k), jnp.int32),
        ],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
    )(queries, corpus)
