"""The one traffic generator: a mix file's parameters -> an open-loop
arrival schedule of sessions.

A mix (`bench/traffic/<name>.json`) declares

  rate_per_s      mean session arrivals per second (Poisson)
  prompt_tokens   first-turn prompt length: a length distribution
  turns           turns per session (1 = single-turn requests)
  output_tokens   new tokens per turn: a length distribution
  gap_ticks       scheduler ticks between the end of one turn and the
                  start of the next: a length distribution (turns > 1)

A length distribution is {"dist": "lognormal", "median", "sigma", "min",
"max"} or {"dist": "uniform", "min", "max"} (integers, both ends in).

Every seed gets the same multiset of sizes and inter-arrival times: a
window of S seconds holds N = round(rate * S) sessions, each quantity is
drawn at the N mid-point quantiles of its distribution, the arrivals
are scaled so that all N fall inside the window, and the seed only
permutes them and draws the token ids. So two seeds offer the same work
in a different order, and their runs differ by the order alone.
"""
from __future__ import annotations

import dataclasses
from statistics import NormalDist
from typing import List, Tuple

import numpy as np


@dataclasses.dataclass(frozen=True)
class Session:
    sid: str
    t_arrival: float                  # seconds after the window opens
    prompt: np.ndarray                # [S] int32
    turns: Tuple[Tuple[int, int], ...]  # (gap_ticks before, new tokens)

    @property
    def new_tokens(self) -> int:
        return sum(n for _, n in self.turns)

    @property
    def context(self) -> int:
        """Positions the session fills by its last token."""
        return len(self.prompt) + self.new_tokens


def quantiles(dist: dict, n: int) -> np.ndarray:
    """`n` integer lengths at the mid-point quantiles (i + 0.5) / n."""
    u = (np.arange(n) + 0.5) / n
    lo, hi = int(dist["min"]), int(dist["max"])
    kind = dist["dist"]
    if kind == "lognormal":
        z = np.array([NormalDist().inv_cdf(p) for p in u])
        x = float(dist["median"]) * np.exp(float(dist["sigma"]) * z)
    elif kind == "uniform":
        x = lo + u * (hi - lo + 1) - 0.5
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    return np.clip(np.rint(x), lo, hi).astype(np.int64)


def n_sessions(mix: dict, seconds: float) -> int:
    """Sessions that arrive in a window of `seconds`."""
    return max(1, round(float(mix["rate_per_s"]) * seconds))


def schedule(mix: dict, seed: int, seconds: float, vocab: int,
             max_len: int) -> List[Session]:
    """Sessions in arrival order, all of them inside the window."""
    n = n_sessions(mix, seconds)
    rng = np.random.default_rng(seed)
    turns = int(mix.get("turns", 1))
    prompts = rng.permutation(quantiles(mix["prompt_tokens"], n))
    outputs = rng.permutation(
        quantiles(mix["output_tokens"], n * turns)).reshape(n, turns)
    if turns > 1:
        gaps = rng.permutation(
            quantiles(mix["gap_ticks"], n * (turns - 1))).reshape(
                n, turns - 1)
        gaps = np.concatenate([np.zeros((n, 1), np.int64), gaps], axis=1)
    else:
        gaps = np.zeros((n, 1), np.int64)
    # exponential inter-arrival times at the same mid-point quantiles;
    # the first session opens the window and the gaps are scaled so that
    # they sum to the window (the last gap would follow the last arrival)
    u = (np.arange(n) + 0.5) / n
    inter = rng.permutation(-np.log1p(-u) / float(mix["rate_per_s"]))
    t = (np.cumsum(inter) - inter) * (seconds / inter.sum())
    out = []
    for i in range(n):
        s = Session(
            sid=f"s{i:05d}", t_arrival=float(t[i]),
            prompt=rng.integers(1, vocab, int(prompts[i])).astype(np.int32),
            turns=tuple((int(g), int(o))
                        for g, o in zip(gaps[i], outputs[i])))
        if s.context >= max_len:
            raise ValueError(f"{s.sid}: context {s.context} does not fit "
                             f"max_len {max_len}")
        out.append(s)
    return out


def prompt_buckets(mix: dict, max_len: int) -> List[int]:
    """Power-of-two prefill buckets that the mix's prompt lengths use
    (the engine pads a prompt of S tokens to min(2^ceil(log2 S),
    max_len - 1))."""
    d = mix["prompt_tokens"]
    lo, hi = int(d["min"]), int(d["max"])
    out, b = [], 1 << max(0, lo - 1).bit_length()
    while True:
        out.append(min(b, max_len - 1))
        if b >= hi:
            return out
        b *= 2
