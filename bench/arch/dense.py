"""Dense decoder with grouped-query attention (mistral-nemo-12b, and MHA
where n_kv equals n_heads): token embedding; per layer
x += Attn(RMSNorm(x)), x += SwiGLU(RMSNorm(x)); final RMSNorm; untied
output head. Attention: q/k/v projections, RoPE on q and k with
rotate-half pairs and inverse frequencies theta^(-2i/head_dim), causal
softmax(q k^T / sqrt(head_dim)) v with each group of n_heads / n_kv
query heads sharing one K/V head, output projection. SwiGLU:
w_out(silu(x w_gate) * (x w_in)).

The float32 forward reads the weights that `reference.init_params`
drew for the program and computes at `highest` matmul precision, one
layer at a time and attention in blocks of queries, so it fits beside
the weights on one chip.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Iterable

import jax
import jax.numpy as jnp
import numpy as np

from bench.counts import BYTES
from bench.reference import F32, Q_BLOCK, _mm, _rms, bucket


def dims(cfg: dict):
    return (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"],
            cfg["intermediate_size"], cfg["num_hidden_layers"],
            cfg["vocab_size"])


# --------------------------------------------------------------- weights
def param_shapes(cfg: dict) -> dict:
    """Leaf shapes, in the program's parameter layout: layers stacked on
    a leading axis; sublayer 0 is attention, sublayer 1 the FFN."""
    d, h, kv, hd, f, n, v = dims(cfg)
    return {
        "embed": (v, d), "unembed": (v, d),
        "groups": {
            "L0S0": {"norm": {"scale": (n, d)},
                     "mixer": {"wq": (n, d, h, hd), "wk": (n, d, kv, hd),
                               "wv": (n, d, kv, hd), "wo": (n, h, hd, d)}},
            "L0S1": {"norm": {"scale": (n, d)},
                     "mixer": {"w_in": (n, d, f), "w_gate": (n, d, f),
                               "w_out": (n, f, d)}}},
        "final_norm": {"scale": (d,)},
    }


def fan_in(path: str, shape) -> int:
    if path.endswith("wo"):
        return shape[1] * shape[2]
    return shape[1]


def program_config(cfg: dict):
    """The program's ModelConfig for a configuration file: the
    registry's architecture at the file's sizes and norm epsilon."""
    from repro.configs import get_config
    base = get_config(cfg["registry"])
    (attn, ffn), = base.pattern
    pattern = ((dataclasses.replace(
        attn, n_heads=cfg["num_attention_heads"],
        n_kv=cfg["num_key_value_heads"], head_dim=cfg["head_dim"]),
        dataclasses.replace(ffn, d_ff=cfg["intermediate_size"])),)
    return dataclasses.replace(
        base, d_model=cfg["hidden_size"], vocab=cfg["vocab_size"],
        n_groups=cfg["num_hidden_layers"], pattern=pattern,
        rope_theta=cfg["rope_theta"], norm_eps=cfg["rms_norm_eps"])


# --------------------------------------------------------------- forward
def _rope(x, theta):
    """x [P, heads, hd] at positions 0..P-1."""
    p, _, hd = x.shape
    half = hd // 2
    inv = theta ** (-jnp.arange(half, dtype=F32) * 2.0 / hd)
    ang = jnp.arange(p, dtype=F32)[:, None] * inv[None]
    sin, cos = jnp.sin(ang)[:, None], jnp.cos(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnames=("cfg_items", "precision"))
def _layer(lp, x, cfg_items, precision):
    cfg = dict(cfg_items)
    d, h, kv, hd, f, _, _ = dims(cfg)
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    p = x.shape[0]
    a, m = lp["L0S0"], lp["L0S1"]
    hn = _rms(x, a["norm"]["scale"], eps)
    q = _rope(_mm("pd,dhk->phk", hn, a["mixer"]["wq"], precision,
                  (1,), (0,)), theta)
    k = _rope(_mm("pd,dgk->pgk", hn, a["mixer"]["wk"], precision,
                  (1,), (0,)), theta)
    v = _mm("pd,dgk->pgk", hn, a["mixer"]["wv"], precision, (1,), (0,))
    r = h // kv
    nb = p // Q_BLOCK
    qb = q.reshape(nb, Q_BLOCK, kv, r, hd)
    kpos = jnp.arange(p)

    def block(args):
        qi, i = args
        s = jnp.einsum("qgrk,tgk->grqt", qi, k) / np.sqrt(hd)
        qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        s = jnp.where(qpos[:, None] >= kpos[None, :], s, -jnp.inf)
        w = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("grqt,tgk->qgrk", w, v)

    o = jax.lax.map(block, (qb, jnp.arange(nb))).reshape(p, h, hd)
    x = x + _mm("phk,hkd->pd", o, a["mixer"]["wo"], precision,
                (1, 2), (0, 1))
    hn = _rms(x, m["norm"]["scale"], eps)
    g = _mm("pd,df->pf", hn, m["mixer"]["w_gate"], precision, (1,), (0,))
    u = _mm("pd,df->pf", hn, m["mixer"]["w_in"], precision, (1,), (0,))
    return x + _mm("pf,fd->pd", jax.nn.silu(g) * u, m["mixer"]["w_out"],
                   precision, (1,), (0,))


@jax.jit
def _embed_rows(table, tokens):
    return table[tokens].astype(F32)


@functools.partial(jax.jit, static_argnames=("eps",))
def _final(scale, x, eps):
    return _rms(x, scale, eps)


def hidden(params, cfg: dict, tokens: np.ndarray, precision: str = "f32"):
    """Final normed hidden states [P, d] for `tokens` right-padded to
    `bucket(len(tokens))` (causal, so padding changes no real row)."""
    p = bucket(len(tokens))
    tok = np.zeros(p, np.int32)
    tok[:len(tokens)] = tokens
    items = tuple(sorted((k, v) for k, v in cfg.items()
                         if isinstance(v, (int, float, str))))
    with jax.default_matmul_precision("highest"):
        x = _embed_rows(params["embed"], jnp.asarray(tok))
        for i in range(cfg["num_hidden_layers"]):
            lp = jax.tree.map(lambda a: a[i], params["groups"])
            x = _layer(lp, x, items, precision)
        return _final(params["final_norm"]["scale"], x,
                      cfg["rms_norm_eps"])


def output_table(params):
    return params["unembed"]


# ----------------------------------------------------------------- work
def layer_params(cfg: dict) -> int:
    """Weights of one decoder layer: attention, SwiGLU FFN, two norms."""
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    attn = d * h * hd + 2 * d * kv * hd + h * hd * d
    ffn = 3 * d * cfg["intermediate_size"]
    return attn + ffn + 2 * d


def decode_step(cfg: dict, lengths: Iterable[int]) -> dict:
    """FLOPs and HBM bytes of one decode step in which the slots with
    `lengths` filled positions each decode one token (attending to
    their filled positions and the new one)."""
    lengths = [int(n) for n in lengths]
    b = len(lengths)
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    n_layers, vocab = cfg["num_hidden_layers"], cfg["vocab_size"]
    w = BYTES[cfg["serving"]["dtype"]]
    ctx = sum(n + 1 for n in lengths)          # positions attended
    matmul = n_layers * (layer_params(cfg) - 2 * d) + d * vocab
    flops = 2 * b * matmul + n_layers * 4 * h * hd * ctx
    weights = (n_layers * layer_params(cfg) + d + vocab * d) * w
    kv_read = n_layers * 2 * kv * hd * sum(lengths) * w
    kv_write = n_layers * 2 * kv * hd * b * w
    io = b * d * w + b * vocab * w             # embedding rows, logits
    return {"flops": flops,
            "bytes": weights + kv_read + kv_write + io}
