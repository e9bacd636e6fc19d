"""Jit'd wrapper: pads the interval batch to a fixed width (stable jit
cache across steps) and runs the sketch-update kernel."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .. import interpret_mode
from .kernel import reuse_sketch_fwd


@functools.partial(jax.jit,
                   static_argnames=("tau0", "decay", "interpret"))
def _update(hist, intervals, class_ids, *, tau0, decay, interpret):
    return reuse_sketch_fwd(hist, intervals, class_ids, tau0=tau0,
                            decay=decay, interpret=interpret)


def reuse_sketch_update(hist, intervals, class_ids, *, tau0: float,
                        decay: float, batch_pad: int = 256):
    """Decayed sketch update for one step's batch.

    hist [C, B] float32; intervals [N] float32 (<= 0 slots skipped);
    class_ids [N] int32. The batch is padded (interval 0, class -1) to
    `batch_pad` rounded up to a power of two of it, so a control plane
    whose per-step batch wanders from 300 to 300k keys compiles
    O(log(max_n / batch_pad)) programs total instead of one per
    multiple of `batch_pad` — pad slots carry class -1 and are skipped,
    so the result is width-independent."""
    hist = jnp.asarray(hist, jnp.float32)
    iv = np.asarray(intervals, np.float32).ravel()
    cls = np.asarray(class_ids, np.int32).ravel()
    if iv.shape != cls.shape:
        raise ValueError("intervals and class_ids must match in length")
    n = int(iv.size)
    if not batch_pad:
        width = max(n, 1)
    else:
        width = int(batch_pad)
        while width < n:
            width *= 2
    pad = width - n
    iv = np.concatenate([iv, np.zeros(pad, np.float32)])
    cls = np.concatenate([cls, np.full(pad, -1, np.int32)])
    return _update(hist, jnp.asarray(iv), jnp.asarray(cls),
                   tau0=float(tau0), decay=float(decay),
                   interpret=interpret_mode())
