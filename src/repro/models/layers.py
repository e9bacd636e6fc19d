"""Shared building blocks: norms, RoPE / M-RoPE, initializers, context."""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..parallel.sharding import Rules


@dataclasses.dataclass
class Ctx:
    """Per-call context threaded through every sublayer."""

    rules: Rules
    mode: str                         # "train" | "prefill" | "decode"
    positions: Optional[jax.Array]    # [B,S] int32, or [3,B,S] for M-RoPE
    cache_index: Optional[jax.Array] = None  # scalar int32 fill pointer
    enc_out: Optional[jax.Array] = None      # encoder stream for cross-attn
    attn_chunk: int = 1024            # kv-block size for chunked attention
    compute_dtype: Any = jnp.bfloat16
    cost_exact: bool = False          # unroll inner loops for cost probes
    # decode in place: the group index (traced or static) whose slice of
    # each stacked [G, B, ...] attention cache this layer writes and
    # reads; None when every cache passed in is the layer's own
    layer: Any = None
    aux: Dict[str, jax.Array] = dataclasses.field(default_factory=dict)

    def add_aux(self, name: str, value):
        self.aux[name] = self.aux.get(name, 0.0) + value


# ---------------------------------------------------------------------------
# Initializers (all take concrete shapes; fan-in scaled normal)
# ---------------------------------------------------------------------------

def dense_init(key, shape, fan_in=None, dtype=jnp.float32):
    fan_in = fan_in if fan_in is not None else shape[0]
    std = 1.0 / np.sqrt(max(fan_in, 1))
    return (jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32)
            * std).astype(dtype)


def embed_init(key, shape, dtype=jnp.float32, std=0.02):
    return (jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32)
            * std).astype(dtype)


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------

def norm_init(d_model: int, kind: str):
    if kind == "rmsnorm":
        return {"scale": jnp.ones((d_model,), jnp.float32)}, {"scale": (None,)}
    return ({"scale": jnp.ones((d_model,), jnp.float32),
             "bias": jnp.zeros((d_model,), jnp.float32)},
            {"scale": (None,), "bias": (None,)})


def apply_norm(params, x, kind: str, eps: float):
    """Norm in f32, output in x.dtype (standard mixed-precision practice)."""
    dtype = x.dtype
    xf = x.astype(jnp.float32)
    if kind == "rmsnorm":
        var = jnp.mean(xf * xf, axis=-1, keepdims=True)
        y = xf * jax.lax.rsqrt(var + eps) * params["scale"]
    else:
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.mean((xf - mu) ** 2, axis=-1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + eps) * params["scale"] \
            + params["bias"]
    return y.astype(dtype)


def rms_norm_heads(x, scale, eps=1e-6):
    """Per-head q/k RMSNorm (qwen3): x [..., head_dim], scale [head_dim]."""
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps) * scale).astype(x.dtype)


# ---------------------------------------------------------------------------
# Rotary position embeddings
# ---------------------------------------------------------------------------

def _rope_angles(positions, dim: int, theta: float):
    """positions [...]; returns (sin, cos) each [..., dim/2] in f32."""
    half = dim // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions[..., None].astype(jnp.float32) * freqs
    return jnp.sin(ang), jnp.cos(ang)


def apply_rope(x, positions, theta: float):
    """x [B,S,H,D]; positions [B,S]. Rotates pairs (x_i, x_{i+half})."""
    d = x.shape[-1]
    sin, cos = _rope_angles(positions, d, theta)       # [B,S,half]
    sin = sin[:, :, None, :]
    cos = cos[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def apply_mrope(x, positions, theta: float, sections):
    """M-RoPE (qwen2-vl): positions [3,B,S] (t,h,w); head_dim/2 split into
    `sections` frequency bands, each rotated by its own position stream."""
    d = x.shape[-1]
    half = d // 2
    assert sum(sections) == half, (sections, half)
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    # pick the position stream per frequency band
    sec_id = jnp.repeat(jnp.arange(len(sections)),
                        jnp.asarray(sections), total_repeat_length=half)
    pos_sel = jnp.take(positions.astype(jnp.float32), sec_id, axis=0)
    ang = jnp.moveaxis(pos_sel, 0, -1) * freqs          # [B,S,half]
    sin, cos = jnp.sin(ang)[:, :, None, :], jnp.cos(ang)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def sinusoidal_positions(n: int, d: int):
    """Absolute sinusoidal table [n, d] (whisper encoder)."""
    pos = np.arange(n)[:, None]
    i = np.arange(d // 2)[None, :]
    ang = pos / np.power(10000.0, 2 * i / d)
    out = np.concatenate([np.sin(ang), np.cos(ang)], axis=-1)
    return jnp.asarray(out, jnp.float32)


# ---------------------------------------------------------------------------
# Activations
# ---------------------------------------------------------------------------

def activation(name: str):
    return {
        "gelu": jax.nn.gelu,
        "relu2": lambda x: jnp.square(jax.nn.relu(x)),
        "silu": jax.nn.silu,
    }[name]
