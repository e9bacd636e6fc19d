"""Operations and bytes that a step's work needs, from shapes alone.

The counts are of the work, not of what the program happens to move: a
decode step reads every weight once (the input embedding only as the
rows it gathers), the K/V of the positions each decoding slot has
filled, and writes one new K/V position per decoding slot and its
logits. A cache read whole, or copied, by the program is not work, so a
later change that stops doing either cannot make these counts stale,
and no share of a roofline built on them can pass 100%.

`cfg` is a configuration file's dict (bench/configs/<name>.json); the
counts of its architecture are in `bench/arch/<arch>.py`.
"""
from __future__ import annotations

import json
import pathlib
from typing import Iterable

from bench import arch

BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def decode_step(cfg: dict, lengths: Iterable[int]) -> dict:
    """FLOPs and HBM bytes of one decode step in which the slots with
    `lengths` filled positions each decode one token, as the
    configuration's architecture (`bench/arch/<arch>.py`) counts them."""
    return arch.of(cfg).decode_step(cfg, lengths)


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
