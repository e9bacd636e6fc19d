"""Sparse-expert decoder (Qwen3-MoE, `Qwen3MoeForCausalLM`): token
embedding; per layer x += Attn(RMSNorm(x)), x += MoE(RMSNorm(x)); final
RMSNorm; untied output head.

Attention: q/k/v projections without bias, an RMSNorm of each head of q
and of k over head_dim, then RoPE on q and k with rotate-half pairs and
inverse frequencies theta^(-2i/head_dim), causal
softmax(q k^T / sqrt(head_dim)) v with each group of n_heads / n_kv
query heads sharing one K/V head, output projection.

MoE: router logits h W_r over all `router_experts` experts (no bias), a
softmax over them, the top `num_experts_per_tok`, and those gates
renormalised to sum to one (`norm_topk_prob`). Each expert is SwiGLU,
w_out(silu(h w_gate) * (h w_in)), of width `moe_intermediate_size`;
there is no shared expert.

One chip is one expert-parallel rank: it holds experts [first_expert,
first_expert + num_experts) of each layer. A token's MoE output here is
the sum, over the experts it chose that are held, of gate * expert(h);
what the other experts would add is left out, in the program and here
alike, and that partial output goes on to the next layer.

The float32 forward reads the weights that `reference.init_params` drew
for the program and computes at `highest` matmul precision, one layer at
a time and attention in blocks of queries. It computes every held
expert for every token on its own and weights each by that token's gate
(zero where the token did not choose it): no capacity, no grouping by
expert, no cache.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Iterable, List

import jax
import jax.numpy as jnp
import numpy as np

from bench.counts import BYTES
from bench.reference import F32, Q_BLOCK, _mm, _rms, bucket


def dims(cfg: dict):
    return (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"],
            cfg["moe_intermediate_size"], cfg["num_experts"],
            cfg["router_experts"], cfg["num_hidden_layers"],
            cfg["vocab_size"])


# --------------------------------------------------------------- weights
def param_shapes(cfg: dict) -> dict:
    """Leaf shapes, in the program's parameter layout: layers stacked on
    a leading axis; sublayer 0 is attention, sublayer 1 the MoE, whose
    experts are the held ones and whose router scores all of them."""
    d, h, kv, hd, f, e, r, n, v = dims(cfg)
    return {
        "embed": (v, d), "unembed": (v, d),
        "groups": {
            "L0S0": {"norm": {"scale": (n, d)},
                     "mixer": {"wq": (n, d, h, hd), "wk": (n, d, kv, hd),
                               "wv": (n, d, kv, hd), "wo": (n, h, hd, d),
                               "q_norm": (n, hd), "k_norm": (n, hd)}},
            "L0S1": {"norm": {"scale": (n, d)},
                     "mixer": {"router": (n, d, r),
                               "w_in": (n, e, d, f), "w_gate": (n, e, d, f),
                               "w_out": (n, e, f, d)}}},
        "final_norm": {"scale": (d,)},
    }


def fan_in(path: str, shape) -> int:
    """Fan-in of the projection leaf at `path` (a key string such as
    "['groups']['L0S0']['mixer']['wo']") of stacked `shape`."""
    leaf = path.rsplit("[", 1)[-1].strip("[]'")
    if leaf == "wo":
        return shape[1] * shape[2]
    if leaf in ("w_in", "w_gate", "w_out"):     # [n, experts, in, out]
        return shape[2]
    return shape[1]


def program_config(cfg: dict):
    """The program's ModelConfig for a configuration file: the
    registry's architecture at the file's sizes, norm epsilon and held
    experts."""
    from repro.configs import get_config
    base = get_config(cfg["registry"])
    (attn, moe), = base.pattern
    pattern = ((dataclasses.replace(
        attn, n_heads=cfg["num_attention_heads"],
        n_kv=cfg["num_key_value_heads"], head_dim=cfg["head_dim"]),
        dataclasses.replace(
            moe, n_experts=cfg["router_experts"],
            top_k=cfg["num_experts_per_tok"],
            d_ff=cfg["moe_intermediate_size"],
            first=cfg["first_expert"], n_held=cfg["num_experts"])),)
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


def _attention(a, x, cfg, precision):
    d, h, kv, hd, *_ = dims(cfg)
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    p = x.shape[0]
    w = a["mixer"]
    hn = _rms(x, a["norm"]["scale"], eps)
    q = _mm("pd,dhk->phk", hn, w["wq"], precision, (1,), (0,))
    k = _mm("pd,dgk->pgk", hn, w["wk"], precision, (1,), (0,))
    v = _mm("pd,dgk->pgk", hn, w["wv"], precision, (1,), (0,))
    q = _rope(_rms(q, w["q_norm"], eps), theta)
    k = _rope(_rms(k, w["k_norm"], eps), theta)
    r = h // kv
    nb = p // Q_BLOCK
    qb = q.reshape(nb, Q_BLOCK, kv, r, hd)
    kpos = jnp.arange(p)

    def block(args):
        qi, i = args
        s = jnp.einsum("qgrk,tgk->grqt", qi, k) / np.sqrt(hd)
        qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        s = jnp.where(qpos[:, None] >= kpos[None, :], s, -jnp.inf)
        return jnp.einsum("grqt,tgk->qgrk", jax.nn.softmax(s, axis=-1), v)

    o = jax.lax.map(block, (qb, jnp.arange(nb))).reshape(p, h, hd)
    return x + _mm("phk,hkd->pd", o, w["wo"], precision, (1, 2), (0, 1))


def held_gates(logits, cfg: dict):
    """Router logits [P, router_experts] -> each token's gate on each
    held expert [P, num_experts]: softmax over all experts, the top
    num_experts_per_tok, renormalised over those chosen (held or not);
    zero where the token did not choose the expert."""
    probs = jax.nn.softmax(logits, axis=-1)
    top, idx = jax.lax.top_k(probs, cfg["num_experts_per_tok"])
    top = top / top.sum(-1, keepdims=True)
    held = cfg["first_expert"] + jnp.arange(cfg["num_experts"])
    chose = idx[:, :, None] == held[None, None, :]
    return (chose * top[:, :, None]).sum(1)


def _moe(m, x, cfg, precision):
    w = m["mixer"]
    hn = _rms(x, m["norm"]["scale"], cfg["rms_norm_eps"])
    gate = held_gates(_mm("pd,de->pe", hn, w["router"], precision, (1,),
                          (0,)), cfg)
    g = _mm("pd,edf->pef", hn, w["w_gate"], precision, (1,), (1,))
    u = _mm("pd,edf->pef", hn, w["w_in"], precision, (1,), (1,))
    y = _mm("pef,efd->ped", jax.nn.silu(g) * u, w["w_out"], precision,
            (2,), (1,))
    return x + (y * gate[:, :, None]).sum(1)


@functools.partial(jax.jit, static_argnames=("cfg_items", "precision"))
def _layer(lp, x, cfg_items, precision):
    cfg = dict(cfg_items)
    x = _attention(lp["L0S0"], x, cfg, precision)
    return _moe(lp["L0S1"], x, cfg, precision)


@jax.jit
def _embed_rows(table, tokens):
    return table[tokens].astype(F32)


@functools.partial(jax.jit, static_argnames=("eps",))
def _final(scale, x, eps):
    return _rms(x, scale, eps)


def hidden(params, cfg: dict, tokens: np.ndarray, precision: str = "f32"):
    """Final normed hidden states [P, d] for `tokens` right-padded to
    `bucket(len(tokens))` (causal, and each token's experts are its own,
    so padding changes no real row)."""
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
    """Weights of one decoder layer that every decode step reads:
    attention with its q/k norms, the router, two norms; no expert."""
    d, h, kv, hd, _, _, r, _, _ = dims(cfg)
    attn = d * h * hd + 2 * d * kv * hd + h * hd * d
    return attn + d * r + 2 * hd + 2 * d


def expert_params(cfg: dict) -> int:
    """Weights of one SwiGLU expert."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def decode_step(cfg: dict, lengths: Iterable[int], reached: int = 0,
                pairs: int = 0) -> dict:
    """FLOPs and HBM bytes of one decode step in which the slots with
    `lengths` filled positions each decode one token (attending to
    their filled positions and the new one). `reached` is the held
    experts that a decoding token was routed to and `pairs` the
    token-expert pairs computed, each summed over the step's layers
    (the engine's `moe_experts_reached` and `moe_pairs`): the step reads
    each reached expert's weights once and computes each pair. Left at
    0, the experts' work is left out, so a share built on it can only
    read low."""
    lengths = [int(n) for n in lengths]
    b = len(lengths)
    d, h, kv, hd, _, _, _, n_layers, vocab = dims(cfg)
    w = BYTES[cfg["serving"]["dtype"]]
    ctx = sum(n + 1 for n in lengths)          # positions attended
    matmul = n_layers * (layer_params(cfg) - 2 * hd - 2 * d) + d * vocab
    flops = (2 * b * matmul + n_layers * 4 * h * hd * ctx
             + 2 * pairs * expert_params(cfg))
    weights = (n_layers * layer_params(cfg) + d + vocab * d
               + reached * expert_params(cfg)) * w
    kv_read = n_layers * 2 * kv * hd * sum(lengths) * w
    kv_write = n_layers * 2 * kv * hd * b * w
    io = b * d * w + b * vocab * w             # embedding rows, logits
    return {"flops": flops,
            "bytes": weights + kv_read + kv_write + io}


def decode_window(cfg: dict, steps: List[Iterable[int]], reached: int,
                  pairs: int) -> dict:
    """FLOPs and bytes of the decode steps `steps` (each its filled
    lengths), with the window's totals of the two expert counters:
    `decode_step` is linear in them, so the window's sum is exact."""
    out = {"flops": 0, "bytes": 0}
    for lengths in steps:
        for k, v in decode_step(cfg, lengths).items():
            out[k] += v
    experts = decode_step(cfg, [], reached, pairs)
    base = decode_step(cfg, [])
    for k in out:
        out[k] += experts[k] - base[k]
    return out
