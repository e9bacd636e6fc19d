"""Roofline-term extraction from compiled XLA artifacts.

Terms per (arch x shape x mesh), all in seconds per step on TPU v5e:

  compute    = HLO_FLOPs / (chips * 197e12)         [bf16 peak]
  memory     = HLO_bytes / (chips * 819e9)          [HBM]
  collective = per-chip wire bytes / 50e9           [ICI per-link]

HLO FLOPs/bytes come from `compiled.cost_analysis()`. Because XLA's cost
analysis counts a `while` (scan) body ONCE regardless of trip count, the
dry-run measures costs with two *unrolled* probe compiles (n_groups=1 and
n_groups=2, cost_exact=True) and extrapolates:

  total(G) = probe(1) + (G - 1) * (probe(2) - probe(1))

which is exact for homogeneous group stacks (all ten assigned archs).
Collective wire bytes are parsed from the post-SPMD HLO text: per-device
shard shapes of every all-gather / all-reduce / reduce-scatter /
all-to-all / collective-permute, scaled by the standard ring factors.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, Optional

# TPU v5e hardware constants (per chip)
PEAK_FLOPS = 197e12          # bf16
HBM_BW = 819e9               # bytes/s
ICI_BW = 50e9                # bytes/s per link

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16,
}

_SHAPE_RE = re.compile(r"(\w+)\[([0-9,]*)\]")
_GROUPS_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUPS_BRACE_RE = re.compile(r"replica_groups=\{\{([^}]*)\}")


def _shape_bytes(shape_str: str) -> float:
    total = 0.0
    for m in _SHAPE_RE.finditer(shape_str):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_wire_bytes(hlo_text: str) -> Dict[str, float]:
    """Per-device wire bytes by collective kind, from post-SPMD HLO.

    Shapes in the partitioned module are per-shard. Ring-algorithm factors:
      all-gather:     result_bytes * (N-1)/N      (result = gathered)
      reduce-scatter: result_bytes * (N-1)        (input = result * N)
      all-reduce:     2 * result_bytes * (N-1)/N
      all-to-all:     result_bytes * (N-1)/N
      collective-permute: result_bytes
    """
    out: Dict[str, float] = {}
    for line in hlo_text.splitlines():
        m = re.search(
            r"=\s*((?:\([^)]*\))|(?:\w+\[[0-9,]*\]\S*))\s+"
            r"(all-reduce|all-gather|reduce-scatter|all-to-all|"
            r"collective-permute)(-start|-done)?\(", line)
        if not m:
            continue
        shape_str, kind, phase = m.group(1), m.group(2), m.group(3)
        if phase == "-done":
            continue                       # counted at -start
        if phase == "-start" and shape_str.startswith("("):
            # async start returns (operand, result[, ...]): count the result
            shapes = _SHAPE_RE.findall(shape_str)
            if len(shapes) >= 2:
                dt, dims = shapes[1]
                shape_str = f"{dt}[{dims}]"
        b = _shape_bytes(shape_str)
        gm = _GROUPS_RE.search(line)
        if gm:
            n = int(gm.group(2))
        else:
            gb = _GROUPS_BRACE_RE.search(line)
            n = len(gb.group(1).split(",")) if gb else 2
        if n <= 1:
            continue
        if kind == "all-gather":
            wire = b * (n - 1) / n
        elif kind == "reduce-scatter":
            wire = b * (n - 1)
        elif kind == "all-reduce":
            wire = 2.0 * b * (n - 1) / n
        elif kind == "all-to-all":
            wire = b * (n - 1) / n
        else:  # collective-permute
            wire = b
        out[kind] = out.get(kind, 0.0) + wire
    out["total"] = sum(out.values())
    return out


@dataclasses.dataclass
class CostTerms:
    flops: float
    bytes_accessed: float
    wire_bytes: float
    wire_by_kind: Dict[str, float]

    def __sub__(self, o: "CostTerms") -> "CostTerms":
        return CostTerms(
            self.flops - o.flops, self.bytes_accessed - o.bytes_accessed,
            self.wire_bytes - o.wire_bytes,
            {k: self.wire_by_kind.get(k, 0.0) - o.wire_by_kind.get(k, 0.0)
             for k in set(self.wire_by_kind) | set(o.wire_by_kind)})

    def __add__(self, o: "CostTerms") -> "CostTerms":
        return CostTerms(
            self.flops + o.flops, self.bytes_accessed + o.bytes_accessed,
            self.wire_bytes + o.wire_bytes,
            {k: self.wire_by_kind.get(k, 0.0) + o.wire_by_kind.get(k, 0.0)
             for k in set(self.wire_by_kind) | set(o.wire_by_kind)})

    def scale(self, f: float) -> "CostTerms":
        return CostTerms(self.flops * f, self.bytes_accessed * f,
                         self.wire_bytes * f,
                         {k: v * f for k, v in self.wire_by_kind.items()})

    def to_dict(self):
        return {"flops": self.flops, "bytes_accessed": self.bytes_accessed,
                "wire_bytes": self.wire_bytes,
                "wire_by_kind": self.wire_by_kind}


def cost_terms(compiled) -> CostTerms:
    ca = compiled.cost_analysis()
    wires = collective_wire_bytes(compiled.as_text())
    return CostTerms(
        flops=float(ca.get("flops", 0.0)),
        bytes_accessed=float(ca.get("bytes accessed", 0.0)),
        wire_bytes=wires["total"],
        wire_by_kind={k: v for k, v in wires.items() if k != "total"})


def extrapolate(probe1: CostTerms, probe2: CostTerms,
                n_groups: int) -> CostTerms:
    """total(G) = probe(1) + (G-1) * marginal."""
    marginal = probe2 - probe1
    return probe1 + marginal.scale(n_groups - 1)


def roofline(total: CostTerms, chips: int, model_flops: float,
             steps_per_call: int = 1) -> Dict[str, float]:
    """The three terms (seconds) + bottleneck + usefulness ratio.

    cost_analysis FLOPs/bytes from a post-SPMD module are PER-DEVICE
    (verified empirically: an 8-way batch-sharded matmul reports 1/8 of the
    logical FLOPs), as are the parsed wire bytes. `model_flops` is global,
    so it is divided by the chip count."""
    t_comp = total.flops / PEAK_FLOPS
    t_mem = total.bytes_accessed / HBM_BW
    t_coll = total.wire_bytes / ICI_BW
    terms = {"compute": t_comp, "memory": t_mem, "collective": t_coll}
    dominant = max(terms, key=terms.get)
    t_model = model_flops / (chips * PEAK_FLOPS)
    t_bound = max(terms.values())
    return {
        **{f"t_{k}": v for k, v in terms.items()},
        "dominant": dominant,
        "model_flops": model_flops,
        "hlo_flops_per_device": total.flops,
        "useful_flop_ratio": model_flops / max(total.flops * chips, 1.0),
        "roofline_fraction": (t_model / t_bound) if t_bound > 0 else 0.0,
        "step_time_bound": t_bound,
    }


def model_flops_for(cfg, shape, mesh_chips: int) -> float:
    """Analytic MODEL_FLOPS: 6*N*D train (fwd+bwd), 2*N*D forward-only,
    with N = active params."""
    n_active = cfg.active_param_count()
    if shape.step == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n_active * tokens
    if shape.step == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n_active * tokens
    tokens = shape.global_batch          # one token per sequence
    return 2.0 * n_active * tokens
