"""Mixture-of-experts sublayer with true expert parallelism.

Routing (top-k over n_experts) happens globally under GSPMD; dispatch,
expert FFN, and combine run inside a `shard_map` over the "model" axis:

  * experts are sharded over "model" (E_loc = E / TP per rank),
  * activations enter replicated over "model" and sharded over the data
    axes, so *dispatch needs no collective at all* — every model rank
    already holds the tokens of its data shard and simply selects the
    choices that route to its local experts,
  * combine is a single psum over "model" (each rank contributes the
    outputs of its experts, zeros elsewhere).

This replaces the classic all_to_all dispatch: with model-replicated
activations the all_to_all is provably redundant (its input is already
resident). The trade is the combine all-reduce of one [T_loc, D] tensor
per layer — measured in the roofline as the MoE collective term.

Capacity is static: C = ceil(capacity_factor * T_loc * top_k / E) per
expert per data shard; overflow tokens are dropped from that expert (the
gate mass renormalizes through the residual stream, GShard-style).

When parameters are FSDP-sharded over "data" (training), expert weights
are all-gathered over the fsdp axis inside the shard_map — the standard
ZeRO-3 gather-at-use, visible as the fsdp collective term.
"""
from __future__ import annotations

import math
from typing import Tuple

import jax
import jax.numpy as jnp

from .config import MoeSpec, ModelConfig
from .layers import Ctx, dense_init
from . import ffn as ffn_mod
from .config import FfnSpec


def init(key, cfg: ModelConfig, spec: MoeSpec):
    d, f, e = cfg.d_model, spec.d_ff, spec.n_experts
    gated = spec.act in ("swiglu", "geglu")
    ks = jax.random.split(key, 5)
    params = {
        "router": dense_init(ks[0], (d, e), fan_in=d),
        "w_in": dense_init(ks[1], (e, d, f), fan_in=d),
        "w_out": dense_init(ks[2], (e, f, d), fan_in=f),
    }
    if gated:
        params["w_gate"] = dense_init(ks[3], (e, d, f), fan_in=d)
    if spec.shared_d_ff:
        params["shared"], _ = ffn_mod.init(
            ks[4], cfg, FfnSpec(d_ff=spec.shared_d_ff, act=spec.act))
    return params, logical(cfg, spec)


def logical(cfg: ModelConfig, spec: MoeSpec):
    out = {
        "router": ("embed", None),
        "w_in": ("experts", "expert_ffn", "moe_ffn"),
        "w_out": ("experts", "moe_ffn", "expert_ffn"),
    }
    if spec.act in ("swiglu", "geglu"):
        out["w_gate"] = ("experts", "expert_ffn", "moe_ffn")
    if spec.shared_d_ff:
        out["shared"] = ffn_mod.logical(
            cfg, FfnSpec(d_ff=spec.shared_d_ff, act=spec.act))
    return out


def _route(params, x, spec: MoeSpec, ctx: Ctx):
    """Global routing. x [B,S,D] -> gates [B,S,K], idx [B,S,K], aux loss."""
    logits = jnp.einsum("bsd,de->bse", x,
                        params["router"].astype(ctx.compute_dtype)
                        ).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, idx = jax.lax.top_k(probs, spec.top_k)
    if spec.top_k > 1:                              # renormalize kept mass
        gates = gates / jnp.maximum(
            gates.sum(-1, keepdims=True), 1e-9)
    # Switch-style load-balance loss: E * sum_e f_e * p_e
    e = spec.n_experts
    sel = jax.nn.one_hot(idx[..., 0], e, dtype=jnp.float32)
    f_e = sel.mean(axis=(0, 1))
    p_e = probs.mean(axis=(0, 1))
    aux = e * jnp.sum(f_e * p_e)
    # router z-loss (stabilizes logits)
    z = jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2)
    ctx.add_aux("moe_aux_loss", spec.aux_loss_weight * aux + 1e-4 * z)
    return gates.astype(ctx.compute_dtype), idx


def _expert_ffn(buf, w_in, w_gate, w_out, act: str):
    """buf [E_loc, C, D] -> [E_loc, C, D]; weights [E_loc, D, F]/[E_loc, F, D]."""
    h = jnp.einsum("ecd,edf->ecf", buf, w_in)
    if w_gate is not None:
        a = jax.nn.silu if act == "swiglu" else jax.nn.gelu
        h = a(jnp.einsum("ecd,edf->ecf", buf, w_gate)) * h
    else:
        h = jax.nn.gelu(h)
    return jnp.einsum("ecf,efd->ecd", h, w_out)


def apply(params, x, spec: MoeSpec, cfg: ModelConfig, ctx: Ctx):
    """x [B,S,D] (normed); returns MoE output [B,S,D]."""
    rules = ctx.rules
    mesh = rules.mesh
    B, S, D = x.shape
    dt = ctx.compute_dtype
    tp = mesh.shape["model"]
    e = spec.n_experts
    assert e % tp == 0, f"{e} experts not divisible by TP={tp}"
    e_loc = e // tp
    dp_axes = tuple(a for a in mesh.axis_names if a != "model")

    x = rules.constrain(x, "batch", None, None)     # gather D, replicate TP
    gates, idx = _route(params, x, spec, ctx)

    t_loc = (B // rules.axis_size(dp_axes)) * S
    cap = max(int(math.ceil(spec.capacity_factor * t_loc * spec.top_k / e)), 4)

    fsdp_ax = rules.table.get("expert_ffn")
    gated = "w_gate" in params

    tokens_gather = rules.table.get("moe_strategy") == "tokens"
    P = jax.sharding.PartitionSpec
    dp = dp_axes if len(dp_axes) > 1 else dp_axes[0]
    tok_spec = P(dp, None, None)
    res_spec = rules.spec_for_shape((B, S, D),
                                    ("batch", None, "res_embed"))

    def _dispatch(xt, gt, it, capacity, e_lo):
        """Shared dispatch: tokens [T,D] -> expert buffer [E_loc,cap,D].
        Returns (buf, ef, pf, keep)."""
        tl_ = xt.shape[0]
        local = (it >= e_lo) & (it < e_lo + e_loc)
        le = jnp.where(local, it - e_lo, 0)
        onehot = (jax.nn.one_hot(le, e_loc, dtype=jnp.int32)
                  * local.astype(jnp.int32)[..., None])       # [T,K,E_loc]
        pos = jnp.cumsum(onehot.reshape(tl_ * spec.top_k, e_loc),
                         axis=0) - 1
        pos = (pos.reshape(tl_, spec.top_k, e_loc) * onehot).sum(-1)
        keep = local & (pos < capacity)
        ef = jnp.where(keep, le, e_loc).reshape(-1)
        pf = jnp.where(keep, pos, capacity).reshape(-1)
        src = jnp.broadcast_to(xt[:, None, :], (tl_, spec.top_k, D))
        buf = jnp.zeros((e_loc, capacity, D), dt).at[ef, pf].add(
            src.reshape(-1, D), mode="drop")
        return buf, ef, pf, keep

    def local_moe(xb, gb, ib, w_in, w_out, w_gate=None):
        # xb [B_loc,S,D]; gb/ib [B_loc,S,K]; weights [E_loc, D(/fsdp), F]
        # cast to the compute dtype BEFORE the fsdp gather: gathering f32
        # master weights would double the wire bytes for no benefit
        w_in, w_out = w_in.astype(dt), w_out.astype(dt)
        w_gate = w_gate.astype(dt) if gated else None
        if fsdp_ax is not None:
            w_in = jax.lax.all_gather(w_in, fsdp_ax, axis=1, tiled=True)
            w_out = jax.lax.all_gather(w_out, fsdp_ax, axis=2, tiled=True)
            if gated:
                w_gate = jax.lax.all_gather(w_gate, fsdp_ax, axis=1,
                                            tiled=True)
        r = jax.lax.axis_index("model")
        tl = xb.shape[0] * xb.shape[1]
        buf, ef, pf, keep = _dispatch(
            xb.reshape(tl, D), None, ib.reshape(tl, spec.top_k), cap,
            r * e_loc)
        out = _expert_ffn(buf, w_in, w_gate, w_out, spec.act)
        # gather back, weight by gate, sum over choices
        got = out.at[ef, pf].get(mode="fill", fill_value=0.0)
        got = got.reshape(tl, spec.top_k, D) \
            * gb.reshape(tl, spec.top_k)[..., None]
        y = got.sum(axis=1)
        # combine: reduce-scatter over TP onto the residual's embed
        # sharding (half the wire of an all-reduce, and the next layer
        # consumes exactly this layout)
        if res_spec[2] == "model":
            y = jax.lax.psum_scatter(y, "model", scatter_dimension=1,
                                     tiled=True)
            return y.reshape(xb.shape[0], S, D // tp)
        y = jax.lax.psum(y, "model")
        return y.reshape(xb.shape)

    def local_moe_tokens(xb, gb, ib, w_in, w_out, w_gate=None):
        """Decode-serving strategy: gather the (few) tokens over the data
        axis instead of gathering expert weights — weights stay resident
        [E/TP, D, F/data]; the expert FFN computes an F-slice and the
        output psums over ("data","model")."""
        w_in, w_out = w_in.astype(dt), w_out.astype(dt)
        w_gate = w_gate.astype(dt) if gated else None
        r = jax.lax.axis_index("model")
        d_rank = jax.lax.axis_index(dp_axes[-1])
        tl = xb.shape[0] * xb.shape[1]
        xg = jax.lax.all_gather(xb.reshape(tl, D), dp_axes[-1],
                                axis=0, tiled=True)
        ig = jax.lax.all_gather(ib.reshape(tl, spec.top_k), dp_axes[-1],
                                axis=0, tiled=True)
        gg = jax.lax.all_gather(gb.reshape(tl, spec.top_k), dp_axes[-1],
                                axis=0, tiled=True)
        tg = xg.shape[0]
        cap_g = max(int(math.ceil(
            spec.capacity_factor * tg * spec.top_k / e)), 4)
        buf, ef, pf, keep = _dispatch(xg, None, ig, cap_g, r * e_loc)
        out = _expert_ffn(buf, w_in, w_gate, w_out, spec.act)  # F-slice
        got = out.at[ef, pf].get(mode="fill", fill_value=0.0)
        got = got.reshape(tg, spec.top_k, D) * gg[..., None]
        y = got.sum(axis=1)                       # partial over F + experts
        y = jax.lax.psum(y, (dp_axes[-1], "model"))
        y = jax.lax.dynamic_slice_in_dim(y, d_rank * tl, tl, axis=0)
        return y.reshape(xb.shape)

    args = [x, gates, idx, params["w_in"], params["w_out"]]
    if tokens_gather:
        w_specs = [P("model", None, dp_axes[-1]),
                   P("model", dp_axes[-1], None)]
        gate_spec = P("model", None, dp_axes[-1])
        body, out_specs = local_moe_tokens, tok_spec
    else:
        w_specs = [P("model", fsdp_ax, None), P("model", None, fsdp_ax)]
        gate_spec = P("model", fsdp_ax, None)
        body = local_moe
        out_specs = P(dp, None, "model") if res_spec[2] == "model" \
            else tok_spec
    in_specs = [tok_spec, tok_spec, tok_spec] + w_specs
    if gated:
        args.append(params["w_gate"])
        in_specs.append(gate_spec)
    y = jax.shard_map(
        body, mesh=mesh, in_specs=tuple(in_specs), out_specs=out_specs,
        check_vma=False)(*args)
    y = rules.constrain(y, "batch", None, "res_embed")

    if spec.shared_d_ff:
        y = y + ffn_mod.apply(params["shared"], x,
                              FfnSpec(d_ff=spec.shared_d_ff, act=spec.act),
                              cfg, ctx)
    return y
