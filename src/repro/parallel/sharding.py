"""Logical-axis sharding rules (MaxText-style) for the production mesh.

Every parameter and activation in the model stack is annotated with
*logical* dimension names ("embed", "heads", "experts", ...). A `Rules`
table maps logical names to mesh axes; `spec_for` resolves a concrete
`PartitionSpec`, silently dropping assignments that do not divide the
dimension or that would reuse a mesh axis twice within one spec (XLA
requires both).

This keeps the model code mesh-agnostic: the same definitions lower on a
single host device (smoke tests), the 16x16 single-pod mesh, and the
2x16x16 multi-pod mesh.
"""
from __future__ import annotations

import dataclasses
from typing import Mapping, Optional, Sequence, Tuple, Union

import jax
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

AxisName = Union[str, Tuple[str, ...]]


@dataclasses.dataclass(frozen=True)
class Rules:
    """Logical-name -> mesh-axis mapping plus the mesh itself.

    `table` values may be a mesh axis name, a tuple of axis names (e.g.
    batch over ("pod", "data")), or None (replicate).
    """

    mesh: Mesh
    table: Mapping[str, Optional[AxisName]]

    def axis_size(self, axis: AxisName) -> int:
        if isinstance(axis, tuple):
            n = 1
            for a in axis:
                n *= self.mesh.shape[a]
            return n
        return self.mesh.shape[axis]

    def spec_for(self, logical: Sequence[Optional[str]]) -> P:
        """Resolve logical dim names to a PartitionSpec.

        Rules:
          * unknown / None names replicate,
          * an assignment is dropped if the mesh axis is already used by an
            earlier dim of this spec,
          * divisibility is NOT checked here (shapes unknown); use
            `spec_for_shape` when the shape is available.
        """
        used: set = set()
        out = []
        for name in logical:
            ax = self.table.get(name) if name else None
            if ax is None:
                out.append(None)
                continue
            parts = ax if isinstance(ax, tuple) else (ax,)
            parts = tuple(a for a in parts if a not in used)
            if not parts:
                out.append(None)
                continue
            used.update(parts)
            out.append(parts if len(parts) > 1 else parts[0])
        return P(*out)

    def spec_for_shape(self, shape: Sequence[int],
                       logical: Sequence[Optional[str]]) -> P:
        """Like spec_for but drops axes that do not divide the dim size."""
        assert len(shape) == len(logical), (shape, logical)
        used: set = set()
        out = []
        for dim, name in zip(shape, logical):
            ax = self.table.get(name) if name else None
            if ax is None:
                out.append(None)
                continue
            parts = tuple(a for a in (ax if isinstance(ax, tuple) else (ax,))
                          if a not in used)
            # greedily keep the longest prefix of axes that divides dim
            while parts and dim % self.axis_size(parts) != 0:
                parts = parts[:-1]
            if not parts:
                out.append(None)
                continue
            used.update(parts)
            out.append(parts if len(parts) > 1 else parts[0])
        return P(*out)

    def sharding(self, shape: Sequence[int],
                 logical: Sequence[Optional[str]]) -> NamedSharding:
        return NamedSharding(self.mesh, self.spec_for_shape(shape, logical))

    def constrain(self, x, *logical: Optional[str]):
        """with_sharding_constraint by logical names (checked against shape)."""
        spec = self.spec_for_shape(x.shape, logical)
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(self.mesh, spec))


# ---------------------------------------------------------------------------
# Standard rule tables
# ---------------------------------------------------------------------------

def _dp_axes(mesh: Mesh) -> AxisName:
    return ("pod", "data") if "pod" in mesh.axis_names else "data"


def train_rules(mesh: Mesh, *, fsdp: bool = True,
                shard_residual_embed: bool = True) -> Rules:
    """Baseline training rules: TP on "model", DP (+pod) on batch, optional
    FSDP-style parameter sharding over "data".

    `shard_residual_embed` shards the scan-carried residual stream's embed
    dim over "model" — bounds stored activations per layer to 1/TP.
    """
    dp = _dp_axes(mesh)
    table = {
        # activations
        "batch": dp,
        "seq": None,
        "res_embed": "model" if shard_residual_embed else None,
        "act_embed": None,
        "act_heads": "model",
        "act_kv": "model",
        "act_qr": "model",    # query-repeat dim claims TP when kv cannot
        "act_ffn": "model",
        "act_experts": "model",
        "act_vocab": "model",
        # params
        "embed": "data" if fsdp else None,     # fsdp axis
        "vocab": "model",
        "heads": "model",
        "kv_heads": "model",
        "head_dim": None,
        "ffn": "model",
        "experts": "model",
        "expert_ffn": "data" if fsdp else None,
        "moe_ffn": None,
        "state": None,
        "conv": None,
        "layers": None,
    }
    return Rules(mesh=mesh, table=table)


def serve_rules(mesh: Mesh, *, moe_tokens_gather: bool = False) -> Rules:
    """Inference rules: no FSDP (params resident), KV cache batch over DP,
    heads over model when divisible, else seq over model.

    `moe_tokens_gather=True` selects the decode-optimized MoE layout:
    expert weights stay fully resident as [E/TP, D, F/data] and the few
    decode tokens are gathered over "data" instead of gathering weights —
    trades the per-layer ~(3*D*F*E/TP) weight all-gather for a
    ~(tokens*D) token gather + output psum."""
    dp = _dp_axes(mesh)
    table = {
        "batch": dp,
        "seq": None,
        "res_embed": "model",
        "act_embed": None,
        "act_heads": "model",
        "act_kv": "model",
        "act_ffn": "model",
        "act_experts": "model",
        "act_vocab": "model",
        # cache layout is [B, KV, S, hd]: kv-heads claim "model" when
        # divisible (dim order gives them priority); otherwise the seq dim
        # takes it (32k/16 = 2k per shard).
        "cache_batch": dp,
        "cache_kv": "model",
        "cache_seq": "model",
        "embed": None,
        "vocab": "model",
        "heads": "model",
        "kv_heads": "model",
        "head_dim": None,
        "ffn": "model",
        "experts": "model",
        # 2D expert sharding at serving: 235B/400B-class MoE weights do
        # not fit at 1/TP per chip. Weight-gather: D over "data", gathered
        # at use. Token-gather (decode): F over "data", weights resident.
        "expert_ffn": None if moe_tokens_gather else "data",
        "moe_ffn": "data" if moe_tokens_gather else None,
        "moe_strategy": "tokens" if moe_tokens_gather else "weights",
        "state": None,
        "conv": None,
        "layers": None,
    }
    return Rules(mesh=mesh, table=table)


def single_device_rules(device=None) -> Rules:
    """Rules over a trivial 1x1 mesh on `device` (default: the first
    device) — one serving replica per chip, smoke tests, examples."""
    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2,
                         devices=[device or jax.devices()[0]])
    return train_rules(mesh, fsdp=False, shard_residual_embed=False)


def params_shardings(rules: Rules, abstract_params, logical_tree):
    """Map a pytree of abstract arrays + parallel logical-name tree to
    NamedShardings."""
    return jax.tree.map(
        lambda a, names: rules.sharding(a.shape, names),
        abstract_params, logical_tree,
        is_leaf=lambda x: isinstance(x, (list, tuple)) and all(
            isinstance(e, (str, type(None))) for e in x))
