"""Jit'd wrapper: hashes keys to candidate buckets, runs the probe kernel."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .. import interpret_mode
from .kernel import LANES, cuckoo_probe_fwd, lane_dense

# lookups per kernel launch: keys and both bucket-id vectors ride in
# scalar memory (SMEM), which holds a few tens of KiB
CHUNK = 2048


def hash_pair(keys, n_buckets: int):
    """Two independent 32-bit multiplicative hashes -> bucket ids."""
    k = keys.astype(jnp.uint32)
    h1 = (k * jnp.uint32(0x9E3779B1)) ^ (k >> 16)
    h2 = (k * jnp.uint32(0x85EBCA77)) ^ (k >> 13)
    return ((h1 % jnp.uint32(n_buckets)).astype(jnp.int32),
            (h2 % jnp.uint32(n_buckets)).astype(jnp.int32))


def cuckoo_probe(keys, bucket_keys, bucket_vals):
    """Batched GET. keys [N] int32; table [n_buckets, slots].

    Returns (found [N] int32, values [N] int32)."""
    nb, slots = bucket_keys.shape
    return _probe(jnp.asarray(keys, jnp.int32), lane_dense(bucket_keys),
                  lane_dense(bucket_vals), n_buckets=nb, slots=slots,
                  interpret=interpret_mode())


@functools.partial(jax.jit, static_argnames=("n_buckets", "slots",
                                             "interpret"))
def _probe(keys, table_keys, table_vals, *, n_buckets: int, slots: int,
           interpret: bool):
    N = keys.shape[0]
    chunk = min(CHUNK, -(-N // LANES) * LANES)
    n_chunks = -(-N // chunk)
    padded = jnp.pad(keys, (0, n_chunks * chunk - N))
    b1, b2 = hash_pair(padded, n_buckets)

    def one(args):
        k, i1, i2 = args
        return cuckoo_probe_fwd(k, i1, i2, table_keys, table_vals,
                                slots=slots, interpret=interpret)

    found, vals = jax.lax.map(
        one, tuple(a.reshape(n_chunks, chunk) for a in (padded, b1, b2)))
    return found.reshape(-1)[:N], vals.reshape(-1)[:N]
