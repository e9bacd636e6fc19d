"""Batched decayed log-bucket reuse-interval sketch update (autopilot).

The autopilot's `ReuseTracker` keeps, per key class (KV sessions, MoE
experts, scan tenants, ...), a histogram over log2-spaced reuse-interval
buckets: bucket b covers [tau0 * 2^b, tau0 * 2^(b+1)). Every decode step
contributes one batch of measured intervals (now - last_seen for each
key the step touched), and the whole sketch ages by a multiplicative
`decay` so the estimate tracks workload drift (diurnal shifts, bursts).

TPU adaptation: a step touches thousands of keys (full slot grids, MoE
routings), so the update is one Pallas launch instead of a host-side
scatter loop. Grid = (C,): program c reduces the whole batch against
its class row — bucketization is a vectorized log2/floor on the VPU and
the scatter-add becomes a dense one-hot [N, B] reduction (B is small,
so the dense form is cheaper than a serialized scatter and has no
write conflicts by construction). The batch is padded to a fixed N by
the wrapper; padding slots carry interval <= 0 and are masked out, the
same convention the numpy oracle uses.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _sketch_kernel(iv_ref, cls_ref, hist_ref, out_ref, *, tau0: float,
                   decay: float, n_buckets: int):
    c = pl.program_id(0)
    iv = iv_ref[...]                              # [N] float32
    cls = cls_ref[...]                            # [N] int32
    valid = (iv > 0) & (cls == c)
    safe = jnp.maximum(iv, jnp.float32(1e-30))
    b = jnp.floor(jnp.log2(safe / jnp.float32(tau0)))
    b = jnp.clip(b, 0, n_buckets - 1).astype(jnp.int32)
    onehot = b[:, None] == jax.lax.broadcasted_iota(
        jnp.int32, (1, n_buckets), 1)             # [N, B]
    counts = jnp.sum(
        jnp.where(onehot & valid[:, None], jnp.float32(1.0),
                  jnp.float32(0.0)), axis=0)
    out_ref[0, :] = jnp.float32(decay) * hist_ref[0, :] + counts


def reuse_sketch_fwd(hist, intervals, class_ids, *, tau0: float,
                     decay: float, interpret: bool):
    """hist [C, B] f32; intervals [N] f32 (<=0 skipped); class_ids [N]
    i32 (rows outside [0, C) skipped). Returns the updated [C, B] hist."""
    C, B = hist.shape
    N = intervals.shape[0]
    kern = functools.partial(_sketch_kernel, tau0=float(tau0),
                             decay=float(decay), n_buckets=B)
    return pl.pallas_call(
        kern,
        grid=(C,),
        in_specs=[
            pl.BlockSpec((N,), lambda c: (0,)),
            pl.BlockSpec((N,), lambda c: (0,)),
            pl.BlockSpec((1, B), lambda c: (c, 0)),
        ],
        out_specs=pl.BlockSpec((1, B), lambda c: (c, 0)),
        out_shape=jax.ShapeDtypeStruct((C, B), jnp.float32),
        interpret=interpret,
    )(intervals, class_ids, hist)
