"""Batched blocked-Cuckoo bucket probe kernel (case study 1, §VII-A).

The SSD-resident table is modeled as an HBM-resident array of buckets
(one bucket == one 512B flash block == `bucket_size` key/value slots).
Each lookup touches exactly two buckets (h1, h2) — the paper's "one or
two SSD block reads per GET".

TPU adaptation of the random-access pattern: bucket indices are computed
on the host side of the kernel (cheap hash) and passed, with the keys,
as *scalar-prefetched* operands; the grid walks lookups one by one and
the BlockSpec index_map uses the prefetched ids to DMA exactly the rows
holding the two candidate buckets into VMEM — the TPU analogue of the
paper's fine-grained 512B random reads (gather-via-scalar-prefetch, the
same mechanism paged attention kernels use).

Layout: the table is viewed lane-dense as [rows, 128] — a 512 B row of
128 int32 slots holds `128 // slots` whole buckets — and one (8, 128)
tile (4 KiB, 8 rows) is the smallest block the TPU lowering takes for
this array. The kernel masks the candidate bucket's row and lanes
inside the tile. Results land in lane-dense [N/128, 1, 128] blocks:
lookup i writes lane i % 128 of a block that stays resident for 128
consecutive grid steps (the lookup axis is sequential).

Grid = (n_lookups,): lookup i compares its key against both candidate
buckets' key slots and emits (found flag, value).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
SUBLANES = 8


def _probe_kernel(b1_idx, b2_idx, keys, bk1_ref, bv1_ref, bk2_ref,
                  bv2_ref, found_ref, val_ref, *, slots: int):
    i = pl.program_id(0)
    key = keys[i]
    row = jax.lax.broadcasted_iota(jnp.int32, (SUBLANES, LANES), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (SUBLANES, LANES), 1)
    per_row = LANES // slots

    def probe(b, k_ref, v_ref):
        mine = (row == (b // per_row) % SUBLANES) & (col // slots
                                                     == b % per_row)
        hit = (k_ref[...] == key) & mine
        found = jnp.max(jnp.max(hit.astype(jnp.int32), axis=0,
                                keepdims=True), axis=1, keepdims=True)
        val = jnp.sum(jnp.sum(jnp.where(hit, v_ref[...], 0), axis=0,
                              keepdims=True), axis=1, keepdims=True)
        return found, val                           # [1, 1] each

    any1, val1 = probe(b1_idx[i], bk1_ref, bv1_ref)
    any2, val2 = probe(b2_idx[i], bk2_ref, bv2_ref)
    mine = col[:1] == i % LANES
    found_ref[0] = jnp.where(mine, jnp.maximum(any1, any2), found_ref[0])
    val_ref[0] = jnp.where(mine, jnp.where(any1 > 0, val1, val2),
                           val_ref[0])


def cuckoo_probe_fwd(keys, b1, b2, table_keys, table_vals, *, slots: int,
                     interpret: bool):
    """keys [N] int32 (0 = empty sentinel), N a multiple of 128; b1,b2
    [N] int32 bucket ids; table_keys/vals the [rows, 128] int32 view of
    the [n_buckets, slots] table (`lane_dense`).

    Returns (found [N] int32, values [N] int32)."""
    N = keys.shape[0]
    if N % LANES:
        raise ValueError(f"lookup count {N} is not a multiple of {LANES}")
    per_tile = LANES // slots * SUBLANES
    by_b1 = pl.BlockSpec((SUBLANES, LANES),
                         lambda i, b1, b2, k: (b1[i] // per_tile, 0))
    by_b2 = pl.BlockSpec((SUBLANES, LANES),
                         lambda i, b1, b2, k: (b2[i] // per_tile, 0))
    out_block = pl.BlockSpec((1, 1, LANES),
                             lambda i, b1, b2, k: (i // LANES, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,            # b1, b2 pick the tiles; keys
        grid=(N,),
        in_specs=[by_b1, by_b1, by_b2, by_b2],
        out_specs=[out_block, out_block],
    )
    found, vals = pl.pallas_call(
        functools.partial(_probe_kernel, slots=slots),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((N // LANES, 1, LANES), jnp.int32),
            jax.ShapeDtypeStruct((N // LANES, 1, LANES), jnp.int32),
        ],
        interpret=interpret,
    )(b1, b2, keys, table_keys, table_vals, table_keys, table_vals)
    return found.reshape(N), vals.reshape(N)


def lane_dense(table) -> np.ndarray:
    """[n_buckets, slots] int32 table -> the kernel's [rows, 128] view,
    padded with empty buckets to whole (8, 128) tiles. Built on the host:
    the TPU stores a [n, 8] int32 array transposed, so the same reshape
    on the device is a relayout that pads every bucket to 128 lanes."""
    table = np.asarray(table, np.int32)
    nb, slots = table.shape
    if LANES % slots:
        raise ValueError(f"bucket width {slots} does not divide {LANES}")
    pad = -nb % (LANES // slots * SUBLANES)
    return np.pad(table, ((0, pad), (0, 0))).reshape(-1, LANES)
