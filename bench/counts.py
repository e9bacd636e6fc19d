"""Operations and bytes that a step's work needs, from shapes alone.

The counts are of the work, not of what the program happens to move: a
decode step reads every weight once (the input embedding only as the
rows it gathers), the K/V of the positions each decoding slot has
filled, and writes one new K/V position per decoding slot and its
logits. A cache read whole, or copied, by the program is not work, so a
later change that stops doing either cannot make these counts stale,
and no share of a roofline built on them can pass 100%.

`cfg` is a configuration file's dict (bench/configs/<name>.json).
"""
from __future__ import annotations

import json
import pathlib
from typing import Iterable

BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


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


def least_seconds(work: dict, peak: dict) -> float:
    """Least time the chip could take: the larger of the compute and
    the memory bound."""
    return max(work["flops"] / peak["bf16_flop_per_s"],
               work["bytes"] / peak["hbm_byte_per_s"])


def peaks(device_kind: str) -> dict:
    """The chip's published peaks; a device not in the table is an
    error, never a default."""
    table = json.loads(
        (pathlib.Path(__file__).parent / "peaks.json").read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device {device_kind!r}; known: "
                       f"{sorted(table)}")
    return table[device_kind]
