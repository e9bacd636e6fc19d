"""Mixture-of-experts sublayer with true expert parallelism.

Routing is global: router logits in `MoeSpec.router_dtype` (float32), a
softmax over all n_experts, top-k, and the k gates renormalized to sum
to one. The experts run inside a `shard_map` over the "model" axis:

  * experts are sharded over "model" (E_loc = E_held / TP per rank),
  * activations enter replicated over "model" and sharded over the data
    axes, so *dispatch needs no collective at all* — every model rank
    already holds the tokens of its data shard and simply selects the
    choices that route to its local experts,
  * combine is a single psum over "model" (each rank contributes the
    outputs of its experts, zeros elsewhere); with one "model" rank
    there is no exchange.

This replaces the classic all_to_all dispatch: with model-replicated
activations the all_to_all is provably redundant (its input is already
resident). The trade is the combine all-reduce of one [T_loc, D] tensor
per layer — measured in the roofline as the MoE collective term.

A layer may hold a contiguous range of the experts the router scores,
[MoeSpec.first, first + n_held): one expert-parallel rank of a wider
deployment. What the experts it does not hold would add is left out.

Serving (prefill and decode) drops no token: each rank sorts the
choices routed to its experts by expert and runs one grouped matmul
(`lax.ragged_dot`) per projection over them, so every choice is
computed whatever the routing skew. Tokens outside `ctx.token_mask` (a
prefill's bucket padding, a decode step's idle slots) are not computed.
That path counts its work into `ctx.counts`: `moe_pairs`, the
token-expert pairs computed, and `moe_experts_reached`, the held
experts that at least one token reached, summed over ranks.

Training keeps a static capacity: C = ceil(capacity_factor * T_loc *
top_k / E) per expert per data shard; overflow tokens are dropped from
that expert (the gate mass renormalizes through the residual stream,
GShard-style).

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
        "w_in": dense_init(ks[1], (spec.held, d, f), fan_in=d),
        "w_out": dense_init(ks[2], (spec.held, f, d), fan_in=f),
    }
    if gated:
        params["w_gate"] = dense_init(ks[3], (spec.held, d, f), fan_in=d)
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
    """Global routing over all n_experts. x [B,S,D] -> float32 gates
    [B,S,K], idx [B,S,K]; adds the aux loss. The logits are computed in
    `spec.router_dtype` at full precision: near-ties among many experts
    would otherwise flip on the compute dtype's rounding."""
    rd = jnp.dtype(spec.router_dtype)
    logits = jnp.einsum("bsd,de->bse", x.astype(rd),
                        params["router"].astype(rd),
                        precision=jax.lax.Precision.HIGHEST,
                        preferred_element_type=jnp.float32)
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
    return gates, idx


def _expert_ffn(buf, w_in, w_gate, w_out, act: str):
    """buf [E_loc, C, D] -> [E_loc, C, D]; weights [E_loc, D, F]/[E_loc, F, D]."""
    h = jnp.einsum("ecd,edf->ecf", buf, w_in)
    if w_gate is not None:
        a = jax.nn.silu if act == "swiglu" else jax.nn.gelu
        h = a(jnp.einsum("ecd,edf->ecf", buf, w_gate)) * h
    else:
        h = jax.nn.gelu(h)
    return jnp.einsum("ecf,efd->ecd", h, w_out)


def _held(xt, gt, it, mt, w_in, w_gate, w_out, lo, act: str):
    """Drop-free expert FFN over the experts [lo, lo + n) held here.

    xt [T,D] tokens; gt [T,K] float32 gates and it [T,K] expert ids of
    their choices; mt [T] the tokens whose work is wanted; weights
    [n, D, F] / [n, F, D]. Every wanted choice routed to a held expert
    is computed: the choices are sorted by expert and each projection is
    one grouped matmul over them (choices of no held expert sort last,
    outside every group). Returns ([T,D] this part of the output, in
    xt's dtype; int32 [2]: token-expert pairs computed, held experts
    reached)."""
    t, k = it.shape
    n = w_in.shape[0]
    with jax.named_scope("moe.dispatch"):
        local = it - lo
        held = (local >= 0) & (local < n) & mt[:, None]
        group = jnp.where(held, local, n).reshape(-1)
        order = jnp.argsort(group, stable=True)
        sizes = jnp.bincount(group, length=n + 1)[:n].astype(jnp.int32)
        rows = xt[order // k]
    with jax.named_scope("moe.experts"):
        h = jax.lax.ragged_dot(rows, w_in, sizes)
        if w_gate is not None:
            a = jax.nn.silu if act == "swiglu" else jax.nn.gelu
            h = a(jax.lax.ragged_dot(rows, w_gate, sizes)) * h
        else:
            h = jax.nn.gelu(h)
        out = jax.lax.ragged_dot(h, w_out, sizes)
    with jax.named_scope("moe.combine"):
        # rows outside every group are undefined: select, never scale
        out = jnp.where(held[..., None],
                        out[jnp.argsort(order)].reshape(t, k, -1), 0)
        y = (out.astype(jnp.float32) * gt[..., None]).sum(axis=1)
    counts = jnp.stack([sizes.sum(), (sizes > 0).sum()]).astype(jnp.int32)
    return y.astype(xt.dtype), counts


def apply(params, x, spec: MoeSpec, cfg: ModelConfig, ctx: Ctx):
    """x [B,S,D] (normed); returns MoE output [B,S,D]. Prefill and
    decode take the drop-free path and add their counts to `ctx`."""
    rules = ctx.rules
    mesh = rules.mesh
    B, S, D = x.shape
    dt = ctx.compute_dtype
    tp = mesh.shape["model"]
    e = spec.n_experts
    serve = ctx.mode != "train"
    if not serve and spec.held != e:
        raise ValueError(f"training routes over {e} experts but this "
                         f"layer holds {spec.held}")
    assert spec.held % tp == 0, f"{spec.held} experts not divisible by TP={tp}"
    e_loc = spec.held // tp
    dp_axes = tuple(a for a in mesh.axis_names if a != "model")

    x = rules.constrain(x, "batch", None, None)     # gather D, replicate TP
    with jax.named_scope("moe.route"):
        gates, idx = _route(params, x, spec, ctx)
    mask = ctx.token_mask if ctx.token_mask is not None \
        else jnp.ones((B, S), bool)
    mask = jnp.broadcast_to(mask, (B, S))

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

    def experts(xt, gt, it, mt, w_in, w_gate, w_out, capacity, e_lo):
        """This rank's part of the output for tokens [T,D]: drop-free
        when serving (with the counts), else by static capacity."""
        if serve:
            return _held(xt, gt, it, mt, w_in, w_gate, w_out, e_lo,
                         spec.act)
        tl_ = xt.shape[0]
        buf, ef, pf, keep = _dispatch(xt, None, it, capacity, e_lo)
        out = _expert_ffn(buf, w_in, w_gate, w_out, spec.act)
        # gather back, weight by gate, sum over choices
        got = out.at[ef, pf].get(mode="fill", fill_value=0.0)
        got = got.reshape(tl_, spec.top_k, D) \
            * gt.astype(dt).reshape(tl_, spec.top_k)[..., None]
        return got.sum(axis=1), None

    def local_moe(xb, gb, ib, mb, w_in, w_out, w_gate=None):
        # xb [B_loc,S,D]; gb/ib [B_loc,S,K]; mb [B_loc,S];
        # weights [E_loc, D(/fsdp), F]
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
        y, counts = experts(
            xb.reshape(tl, D), gb.reshape(tl, spec.top_k),
            ib.reshape(tl, spec.top_k), mb.reshape(tl), w_in, w_gate,
            w_out, cap, spec.first + r * e_loc)
        if counts is not None and mesh.size > 1:
            counts = jax.lax.psum(counts, mesh.axis_names)
        # combine: reduce-scatter over TP onto the residual's embed
        # sharding (half the wire of an all-reduce, and the next layer
        # consumes exactly this layout)
        if res_spec[2] == "model":
            y = jax.lax.psum_scatter(y, "model", scatter_dimension=1,
                                     tiled=True)
            y = y.reshape(xb.shape[0], S, D // tp)
        else:
            if tp > 1:
                y = jax.lax.psum(y, "model")
            y = y.reshape(xb.shape)
        return y if counts is None else (y, counts)

    def local_moe_tokens(xb, gb, ib, mb, w_in, w_out, w_gate=None):
        """Decode-serving strategy: gather the (few) tokens over the data
        axis instead of gathering expert weights — weights stay resident
        [E/TP, D, F/data]; the expert FFN computes an F-slice and the
        output psums over ("data","model")."""
        w_in, w_out = w_in.astype(dt), w_out.astype(dt)
        w_gate = w_gate.astype(dt) if gated else None
        r = jax.lax.axis_index("model")
        d_rank = jax.lax.axis_index(dp_axes[-1])
        tl = xb.shape[0] * xb.shape[1]

        def gather(a, *trailing):
            return jax.lax.all_gather(a.reshape(tl, *trailing),
                                      dp_axes[-1], axis=0, tiled=True)

        xg, ig, gg, mg = (gather(xb, D), gather(ib, spec.top_k),
                          gather(gb, spec.top_k), gather(mb))
        tg = xg.shape[0]
        cap_g = max(int(math.ceil(
            spec.capacity_factor * tg * spec.top_k / e)), 4)
        # partial over F + experts
        y, counts = experts(xg, gg, ig, mg, w_in, w_gate, w_out, cap_g,
                            spec.first + r * e_loc)
        y = jax.lax.psum(y, (dp_axes[-1], "model"))
        y = jax.lax.dynamic_slice_in_dim(y, d_rank * tl, tl, axis=0)
        y = y.reshape(xb.shape)
        if counts is None:
            return y
        # each rank of dp_axes[-1] counted the same gathered tokens
        return y, jax.lax.psum(counts, tuple(
            a for a in mesh.axis_names if a != dp_axes[-1]))

    args = [x, gates, idx, mask, params["w_in"], params["w_out"]]
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
    in_specs = [tok_spec, tok_spec, tok_spec, P(dp, None)] + w_specs
    if gated:
        args.append(params["w_gate"])
        in_specs.append(gate_spec)
    if serve:
        out_specs = (out_specs, P())
    y = jax.shard_map(
        body, mesh=mesh, in_specs=tuple(in_specs), out_specs=out_specs,
        check_vma=False)(*args)
    if serve:
        y, counts = y
        ctx.add_count("moe_pairs", counts[0])
        ctx.add_count("moe_experts_reached", counts[1])
    y = rules.constrain(y, "batch", None, "res_embed")

    if spec.shared_d_ff:
        y = y + ffn_mod.apply(params["shared"], x,
                              FfnSpec(d_ff=spec.shared_d_ff, act=spec.act),
                              cfg, ctx)
    return y
