"""Model assembly: embedding -> scanned group stack (+tail) -> norm -> logits.

Three entry points share one stack implementation:

  forward(...)   train-mode forward, full-sequence logits (via loss_and_aux)
  prefill(...)   fills KV/state caches, returns last-position logits
  decode(...)    one-token step against the caches

The layer stack lowers as a single `lax.scan` over stacked group params, so
HLO size / compile time are depth-independent. Shared sublayers (zamba2's
shared attention) live outside the scan and are closed over — XLA hoists
them as loop invariants. Heterogeneous remainders go in `cfg.tail`.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..parallel.sharding import Rules
from . import attention, ffn, moe, ssm, xlstm
from .config import (AttnSpec, FfnSpec, MLstmSpec, Mamba2Spec, ModelConfig,
                     MoeSpec, SLstmSpec)
from .layers import Ctx, apply_norm, embed_init, norm_init, \
    sinusoidal_positions


# ---------------------------------------------------------------------------
# Sublayer dispatch
# ---------------------------------------------------------------------------

_INIT = {
    "attn": attention.init,
    "ffn": ffn.init,
    "moe": moe.init,
    "mamba2": ssm.init,
    "mlstm": xlstm.init_mlstm,
    "slstm": xlstm.init_slstm,
}

_LOGICAL = {
    "attn": attention.logical,
    "ffn": ffn.logical,
    "moe": moe.logical,
    "mamba2": ssm.logical,
    "mlstm": xlstm.logical_mlstm,
    "slstm": xlstm.logical_slstm,
}

_HAS_CACHE = {"attn", "mamba2", "mlstm", "slstm"}


def _sub_init(key, cfg: ModelConfig, spec):
    k1, k2 = jax.random.split(key)
    mixer, _ = _INIT[spec.kind](k1, cfg, spec)
    nrm, _ = norm_init(cfg.d_model, cfg.norm)
    return {"norm": nrm, "mixer": mixer}


def _sub_logical(cfg: ModelConfig, spec):
    _, nrm_log = norm_init(cfg.d_model, cfg.norm)
    return {"norm": nrm_log, "mixer": _LOGICAL[spec.kind](cfg, spec)}


def _sub_apply(params, x, spec, cfg: ModelConfig, ctx: Ctx, cache=None):
    h = apply_norm(params["norm"], x, cfg.norm, cfg.norm_eps)
    # explicit TP gather point on the bf16 norm output: without this, SPMD
    # is free to hoist the layer-input all-gather above the f32->bf16
    # convert and move the activations at twice the wire bytes
    h = ctx.rules.constrain(h, "batch", None, "act_embed")
    kind = spec.kind
    if kind == "attn":
        with jax.named_scope("attention"):
            out, nc = attention.apply(params["mixer"], h, spec, cfg, ctx,
                                      cache)
    elif kind == "ffn":
        with jax.named_scope("ffn"):
            out, nc = ffn.apply(params["mixer"], h, spec, cfg, ctx), None
    elif kind == "moe":
        out, nc = moe.apply(params["mixer"], h, spec, cfg, ctx), None
    elif kind == "mamba2":
        out, nc = ssm.apply(params["mixer"], h, spec, cfg, ctx, cache)
    elif kind == "mlstm":
        out, nc = xlstm.apply_mlstm(params["mixer"], h, spec, cfg, ctx, cache)
    elif kind == "slstm":
        out, nc = xlstm.apply_slstm(params["mixer"], h, spec, cfg, ctx, cache)
    else:
        raise ValueError(kind)
    # constrain the sublayer output to the residual layout BEFORE the add:
    # the out-projections contract TP-sharded dims (heads/ffn), so this
    # lets SPMD emit a reduce-scatter straight into the res_embed sharding
    # instead of a full all-reduce followed by a re-slice
    out = ctx.rules.constrain(out, "batch", None, "res_embed")
    return x + out, nc


def _sub_cache(cfg, spec, batch, max_len, dtype, enc_len):
    if spec.kind == "attn":
        return attention.init_cache(cfg, spec, batch, max_len, dtype, enc_len)
    # recurrent states stay in their native dtypes (int8 applies to KV only)
    state_dtype = jnp.bfloat16 if dtype == jnp.int8 else dtype
    if spec.kind == "mamba2":
        return ssm.init_cache(cfg, spec, batch, state_dtype)
    if spec.kind == "mlstm":
        return xlstm.init_mlstm_cache(cfg, spec, batch, state_dtype)
    if spec.kind == "slstm":
        return xlstm.init_slstm_cache(cfg, spec, batch, state_dtype)
    return None


def _sub_cache_logical(spec, kv_quant=False):
    if spec.kind == "attn":
        return attention.cache_logical(spec, quantized=kv_quant)
    if spec.kind == "mamba2":
        return ssm.cache_logical(spec)
    if spec.kind == "mlstm":
        return xlstm.mlstm_cache_logical(spec)
    if spec.kind == "slstm":
        return xlstm.slstm_cache_logical(spec)
    return None


def _key(li: int, si: int) -> str:
    return f"L{li}S{si}"


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------

def init_params(key, cfg: ModelConfig):
    """Returns (params, logical) pytrees. Group params are stacked [G, ...]."""
    keys = jax.random.split(key, 8)
    params: Dict[str, Any] = {}
    logical: Dict[str, Any] = {}

    params["embed"] = embed_init(keys[0], (cfg.vocab, cfg.d_model))
    logical["embed"] = ("vocab", "embed")
    if not cfg.tie_embeddings:
        params["unembed"] = embed_init(keys[6], (cfg.vocab, cfg.d_model))
        logical["unembed"] = ("vocab", "embed")

    def _is_names(v):
        return isinstance(v, tuple) and all(
            isinstance(e, (str, type(None))) for e in v)

    def _stacked_logical(spec):
        return jax.tree.map(lambda names: ("layers",) + tuple(names),
                            _sub_logical(cfg, spec), is_leaf=_is_names)

    shared_specs = [(li, si, s) for li, layer in enumerate(cfg.pattern)
                    for si, s in enumerate(layer)
                    if getattr(s, "shared", False)]
    if shared_specs:
        params["shared"], logical["shared"] = {}, {}
        for (li, si, s), k in zip(
                shared_specs, jax.random.split(keys[1], len(shared_specs))):
            params["shared"][_key(li, si)] = _sub_init(k, cfg, s)
            logical["shared"][_key(li, si)] = _sub_logical(cfg, s)

    def init_group(k):
        out = {}
        n_sub = sum(len(layer) for layer in cfg.pattern)
        ks = jax.random.split(k, n_sub)
        i = 0
        for li, layer in enumerate(cfg.pattern):
            for si, s in enumerate(layer):
                if not getattr(s, "shared", False):
                    out[_key(li, si)] = _sub_init(ks[i], cfg, s)
                i += 1
        return out

    params["groups"] = jax.vmap(init_group)(
        jax.random.split(keys[2], cfg.n_groups))
    logical["groups"] = {
        _key(li, si): _stacked_logical(s)
        for li, layer in enumerate(cfg.pattern)
        for si, s in enumerate(layer) if not getattr(s, "shared", False)}

    if cfg.tail:
        params["tail"], logical["tail"] = {}, {}
        flat_tail = [(li, si, s) for li, layer in enumerate(cfg.tail)
                     for si, s in enumerate(layer)]
        for (li, si, s), k in zip(
                flat_tail, jax.random.split(keys[3], len(flat_tail))):
            params["tail"][_key(li, si)] = _sub_init(k, cfg, s)
            logical["tail"][_key(li, si)] = _sub_logical(cfg, s)

    params["final_norm"], logical["final_norm"] = norm_init(
        cfg.d_model, cfg.norm)

    if cfg.encoder is not None:
        enc = cfg.encoder

        def init_enc_group(k):
            out = {}
            flat = [(li, si, s) for li, layer in enumerate(enc.pattern)
                    for si, s in enumerate(layer)]
            for (li, si, s), kk in zip(flat,
                                       jax.random.split(k, len(flat))):
                out[_key(li, si)] = _sub_init(kk, cfg, s)
            return out

        egp = jax.vmap(init_enc_group)(
            jax.random.split(keys[4], enc.n_groups))
        elog = {_key(li, si): _stacked_logical(s)
                for li, layer in enumerate(enc.pattern)
                for si, s in enumerate(layer)}
        fn, fnl = norm_init(cfg.d_model, cfg.norm)
        params["encoder"] = {"groups": egp, "final_norm": fn}
        logical["encoder"] = {"groups": elog, "final_norm": fnl}

    return params, logical


def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               dtype=jnp.bfloat16, enc_len: Optional[int] = None):
    """Zero caches, grouped like params: {"groups": {key: [G,...]}, "tail"}."""
    enc_len = enc_len if enc_len is not None else (
        cfg.encoder.n_frames if cfg.encoder else 0)
    groups = {}
    for li, layer in enumerate(cfg.pattern):
        for si, s in enumerate(layer):
            c = _sub_cache(cfg, s, batch, max_len, dtype, enc_len)
            if c is not None:
                groups[_key(li, si)] = jax.tree.map(
                    lambda a: jnp.broadcast_to(
                        a, (cfg.n_groups,) + a.shape).copy(), c)
    tail = {}
    for li, layer in enumerate(cfg.tail):
        for si, s in enumerate(layer):
            c = _sub_cache(cfg, s, batch, max_len, dtype, enc_len)
            if c is not None:
                tail[_key(li, si)] = c
    return {"groups": groups, "tail": tail}


def cache_logical_tree(cfg: ModelConfig, kv_quant: bool = False):
    groups, tail = {}, {}
    for li, layer in enumerate(cfg.pattern):
        for si, s in enumerate(layer):
            lg = _sub_cache_logical(s, kv_quant)
            if lg is not None:
                groups[_key(li, si)] = jax.tree.map(
                    lambda names: ("layers",) + tuple(names), lg,
                    is_leaf=lambda v: isinstance(v, tuple) and all(
                        isinstance(e, (str, type(None))) for e in v))
    for li, layer in enumerate(cfg.tail):
        for si, s in enumerate(layer):
            lg = _sub_cache_logical(s, kv_quant)
            if lg is not None:
                tail[_key(li, si)] = lg
    return {"groups": groups, "tail": tail}


# ---------------------------------------------------------------------------
# Stack
# ---------------------------------------------------------------------------

def _apply_group(pattern, gparams, shared, x, cfg, ctx: Ctx, gcache):
    new_cache = {}
    ctx = dataclasses.replace(ctx, aux={})
    for li, layer in enumerate(pattern):
        for si, spec in enumerate(layer):
            k = _key(li, si)
            p = shared[k] if getattr(spec, "shared", False) else gparams[k]
            c = gcache.get(k) if gcache else None
            x, nc = _sub_apply(p, x, spec, cfg, ctx, c)
            if nc is not None:
                new_cache[k] = nc
    x = ctx.rules.constrain(x, "batch", None, "res_embed")
    aux = functools.reduce(jnp.add, ctx.aux.values(), jnp.zeros((), jnp.float32))
    return x, new_cache, aux


def _attn_keys(cfg: ModelConfig):
    return [_key(li, si) for li, layer in enumerate(cfg.pattern)
            for si, s in enumerate(layer) if s.kind == "attn"]


def run_stack(params, x, cfg: ModelConfig, ctx: Ctx, caches=None,
              remat: bool = False, remat_policy=None,
              unroll: bool = False):
    """Returns (x, new_caches, aux_loss).

    `unroll=True` replaces the group scan with a python loop — used by the
    roofline cost probes (HLO cost analysis counts a scan body once, so
    probes compile unrolled G=1 and G=2 stacks and take the marginal).

    A decode step carries the stacked attention caches through the layer
    loop as state instead of scanning over them: each layer scatters its
    new rows into the stack and reads its slice back, so no layer's cache
    is copied, sharded or not. Recurrent states are still sliced per
    layer and stacked back."""
    shared = params.get("shared", {})
    gcaches = dict(caches["groups"]) if caches else {}
    stack = {}
    if ctx.mode == "decode" and caches:
        stack = {k: gcaches.pop(k) for k in _attn_keys(cfg)}

    def group_fn(gp, h, gc, stk, layer):
        lctx = ctx if layer is None else dataclasses.replace(ctx, layer=layer)
        h, nc, aux = _apply_group(cfg.pattern, gp, shared, h, cfg, lctx,
                                  {**gc, **stk})
        return h, {k: nc.pop(k) for k in stk}, nc, aux

    wrapped = jax.checkpoint(group_fn, policy=remat_policy) if remat \
        else group_fn

    if unroll:
        aux = jnp.zeros((), jnp.float32)
        ncs = []
        for i in range(cfg.n_groups):
            gp = jax.tree.map(lambda a: a[i], params["groups"])
            gc = jax.tree.map(lambda a: a[i], gcaches)
            x, stack, nc, aux_d = wrapped(gp, x, gc, stack,
                                          i if stack else None)
            aux = aux + aux_d
            ncs.append(nc)
        new_gcaches = jax.tree.map(lambda *a: jnp.stack(a), *ncs)
    else:
        def body(carry, xs):
            h, aux, stk = carry
            gp, gc, layer = xs
            h, stk, nc, aux_d = wrapped(gp, h, gc, stk, layer)
            return (h, aux + aux_d, stk), nc

        xs = (params["groups"], gcaches,
              jnp.arange(cfg.n_groups) if stack else None)
        with jax.named_scope("layers"):
            (x, aux, stack), new_gcaches = jax.lax.scan(
                body, (x, jnp.zeros((), jnp.float32), stack), xs)

    new_tail = {}
    tcaches = caches["tail"] if caches else None
    for li, layer in enumerate(cfg.tail):
        for si, spec in enumerate(layer):
            k = _key(li, si)
            p = params["tail"][k]
            c = tcaches.get(k) if tcaches else None
            ctx2 = dataclasses.replace(ctx, aux={})
            x, nc = _sub_apply(p, x, spec, cfg, ctx2, c)
            aux = aux + functools.reduce(
                jnp.add, ctx2.aux.values(), jnp.zeros((), jnp.float32))
            if nc is not None:
                new_tail[k] = nc

    new_caches = ({"groups": {**new_gcaches, **stack}, "tail": new_tail}
                  if caches is not None else None)
    return x, new_caches, aux


def run_encoder(params, frames, cfg: ModelConfig, ctx: Ctx):
    """Whisper-style encoder over precomputed frame embeddings [B,F,D]."""
    enc = cfg.encoder
    B, F, D = frames.shape
    x = frames + sinusoidal_positions(F, D).astype(frames.dtype)[None]
    x = ctx.rules.constrain(x, "batch", None, "res_embed")
    ectx = dataclasses.replace(
        ctx, positions=jnp.broadcast_to(jnp.arange(F)[None], (B, F)),
        aux={})

    def body(h, gp):
        h, _, _ = _apply_group(enc.pattern, gp, {}, h, cfg, ectx, None)
        return h, None

    x, _ = jax.lax.scan(body, x, params["encoder"]["groups"])
    return apply_norm(params["encoder"]["final_norm"], x, cfg.norm,
                      cfg.norm_eps)


# ---------------------------------------------------------------------------
# Entry points
# ---------------------------------------------------------------------------

def _embed_tokens(params, cfg: ModelConfig, tokens, dtype):
    with jax.named_scope("embed"):
        x = params["embed"].astype(dtype)[tokens]
        if cfg.embed_scale:
            x = x * np.sqrt(cfg.d_model)
    return x


def _default_positions(cfg: ModelConfig, B: int, S: int, offset=0):
    pos = jnp.broadcast_to(jnp.arange(S)[None] + offset, (B, S))
    if any(s.kind == "attn" and s.rope == "mrope"
           for _, _, _, s in cfg.sublayers()):
        pos = jnp.broadcast_to(pos[None], (3, B, S))
    return pos


def _logits(params, cfg: ModelConfig, x, ctx: Ctx):
    table = params.get("unembed", params["embed"])
    logits = jnp.einsum("bsd,vd->bsv", x, table.astype(x.dtype))
    if cfg.final_logit_softcap:
        logits = jnp.tanh(logits / cfg.final_logit_softcap) \
            * cfg.final_logit_softcap
    return ctx.rules.constrain(logits, "batch", None, "act_vocab")


def forward(params, cfg: ModelConfig, rules: Rules, batch: Dict[str, Any],
            compute_dtype=jnp.bfloat16, remat: bool = True,
            remat_policy=None, cost_exact: bool = False,
            unroll: bool = False):
    """Train-mode forward. Returns (logits [B,S,V], aux_loss)."""
    tokens = batch["tokens"]
    B, S_tok = tokens.shape
    x = _embed_tokens(params, cfg, tokens, compute_dtype)
    if cfg.modality == "vlm" and "vision_embeds" in batch:
        x = jnp.concatenate(
            [batch["vision_embeds"].astype(compute_dtype), x], axis=1)
    B, S, _ = x.shape
    positions = batch.get("positions")
    if positions is None:
        positions = _default_positions(cfg, B, S)
    ctx = Ctx(rules=rules, mode="train", positions=positions,
              compute_dtype=compute_dtype, cost_exact=cost_exact)
    if cfg.encoder is not None:
        ctx.enc_out = run_encoder(params, batch["frames"].astype(
            compute_dtype), cfg, ctx)
    x = rules.constrain(x, "batch", None, "res_embed")
    x, _, aux = run_stack(params, x, cfg, ctx, caches=None, remat=remat,
                          remat_policy=remat_policy, unroll=unroll)
    x = apply_norm(params["final_norm"], x, cfg.norm, cfg.norm_eps)
    return _logits(params, cfg, x, ctx), aux


def reference_logits(params, cfg: ModelConfig, rules: Rules, tokens,
                     vocab_chunk: int = 16384):
    """float32 reference of `forward` that fits beside a served model:
    the same embedding, group stack and logits, computed in float32 from
    (say) bf16 `params` cast up one group — and one vocab chunk of the
    output table — at a time, so the float32 copy of the whole tree is
    never held. Run it under `jax.default_matmul_precision("highest")`
    for a full-precision reference. tokens [B,S] -> logits [B,S,V] f32."""
    if cfg.encoder is not None or cfg.modality == "vlm":
        raise ValueError("reference_logits covers decoder-only text models")
    f32 = jnp.float32
    up = functools.partial(jax.tree.map, lambda a: a.astype(f32))
    B, S = tokens.shape
    x = params["embed"][tokens].astype(f32)     # gather, then cast: exact
    if cfg.embed_scale:
        x = x * np.sqrt(cfg.d_model)
    ctx = Ctx(rules=rules, mode="train",
              positions=_default_positions(cfg, B, S), compute_dtype=f32)

    @jax.jit
    def group(gp, shared, h):
        return _apply_group(cfg.pattern, up(gp), up(shared), h, cfg, ctx,
                            None)[0]

    for i in range(cfg.n_groups):
        x = group(jax.tree.map(lambda a: a[i], params["groups"]),
                  params.get("shared", {}), x)

    @jax.jit
    def tail_and_norm(tail, fn, h):
        for li, layer in enumerate(cfg.tail):
            for si, spec in enumerate(layer):
                h, _ = _sub_apply(up(tail[_key(li, si)]), h, spec, cfg,
                                  dataclasses.replace(ctx, aux={}))
        return apply_norm(up(fn), h, cfg.norm, cfg.norm_eps)

    x = tail_and_norm(params.get("tail", {}), params["final_norm"], x)

    @jax.jit
    def logits(rows, h):
        out = jnp.einsum("bsd,vd->bsv", h, rows.astype(f32))
        if cfg.final_logit_softcap:
            out = jnp.tanh(out / cfg.final_logit_softcap) \
                * cfg.final_logit_softcap
        return out

    table = params.get("unembed", params["embed"])
    return jnp.concatenate(
        [logits(table[lo:lo + vocab_chunk], x)
         for lo in range(0, cfg.vocab, vocab_chunk)], axis=-1)


def loss_and_aux(params, cfg: ModelConfig, rules: Rules, batch,
                 compute_dtype=jnp.bfloat16, remat: bool = True,
                 remat_policy=None, z_loss: float = 1e-4,
                 cost_exact: bool = False, unroll: bool = False):
    """Next-token CE (+z-loss, +MoE aux). Returns (loss, metrics)."""
    logits, aux = forward(params, cfg, rules, batch, compute_dtype,
                          remat, remat_policy, cost_exact, unroll)
    tokens = batch["tokens"]
    B, S_tok = tokens.shape
    S = logits.shape[1]
    off = S - S_tok                      # vision prefix (loss on text only)
    logits_t = logits[:, off:off + S_tok - 1]
    targets = tokens[:, 1:]
    lf = logits_t.astype(jnp.float32)
    lse = jax.nn.logsumexp(lf, axis=-1)
    gold = jnp.take_along_axis(lf, targets[..., None], axis=-1)[..., 0]
    mask = batch.get("loss_mask")
    mask = jnp.ones_like(gold) if mask is None else \
        mask[:, 1:].astype(jnp.float32)
    denom = jnp.maximum(mask.sum(), 1.0)
    ce = (((lse - gold) * mask).sum() / denom)
    zl = z_loss * (((lse ** 2) * mask).sum() / denom)
    loss = ce + zl + aux
    return loss, {"ce": ce, "z_loss": zl, "aux": aux,
                  "ppl_proxy": jnp.exp(jnp.minimum(ce, 20.0))}


def prefill(params, cfg: ModelConfig, rules: Rules, batch, cache,
            compute_dtype=jnp.bfloat16, cost_exact: bool = False,
            unroll: bool = False, last_index=None):
    """Fill caches from a prompt. Returns (new_cache, last_logits [B,V]).

    `last_index` (traced scalar) selects which position's logits to
    return instead of the final one — the serving engine right-pads
    prompts to power-of-two buckets (one compile per bucket instead of
    one per exact length) and still needs the logits of the last *real*
    token; causality keeps positions < last_index unaffected by pads."""
    tokens = batch["tokens"]
    x = _embed_tokens(params, cfg, tokens, compute_dtype)
    if cfg.modality == "vlm" and "vision_embeds" in batch:
        x = jnp.concatenate(
            [batch["vision_embeds"].astype(compute_dtype), x], axis=1)
    B, S, _ = x.shape
    positions = batch.get("positions")
    if positions is None:
        positions = _default_positions(cfg, B, S)
    ctx = Ctx(rules=rules, mode="prefill", positions=positions,
              cache_index=jnp.zeros((), jnp.int32),
              compute_dtype=compute_dtype, cost_exact=cost_exact)
    if cfg.encoder is not None:
        ctx.enc_out = run_encoder(params, batch["frames"].astype(
            compute_dtype), cfg, ctx)
    x = rules.constrain(x, "batch", None, "res_embed")
    x, new_cache, _ = run_stack(params, x, cfg, ctx, caches=cache,
                                unroll=unroll)
    if last_index is None:
        x_last = x[:, -1:]
    else:
        x_last = jax.lax.dynamic_slice_in_dim(
            x, jnp.asarray(last_index, jnp.int32), 1, axis=1)
    with jax.named_scope("head"):
        x_last = apply_norm(params["final_norm"], x_last, cfg.norm,
                            cfg.norm_eps)
        logits = _logits(params, cfg, x_last, ctx)[:, 0]
    return new_cache, logits


def decode_step(params, cfg: ModelConfig, rules: Rules, token, cache,
                index, compute_dtype=jnp.bfloat16,
                cost_exact: bool = False, unroll: bool = False):
    """One decode step. token [B,1] int32; index scalar int32 (fill point).
    Returns (new_cache, logits [B,V])."""
    B = token.shape[0]
    x = _embed_tokens(params, cfg, token, compute_dtype)
    idx = jnp.asarray(index)
    pos = (idx[:, None] if idx.ndim == 1
           else jnp.broadcast_to(idx[None, None], (B, 1)))
    if any(s.kind == "attn" and s.rope == "mrope"
           for _, _, _, s in cfg.sublayers()):
        pos = jnp.broadcast_to(pos[None], (3, B, 1))
    ctx = Ctx(rules=rules, mode="decode", positions=pos, cache_index=index,
              compute_dtype=compute_dtype)
    x = rules.constrain(x, "batch", None, "res_embed")
    x, new_cache, _ = run_stack(params, x, cfg, ctx, caches=cache,
                                unroll=unroll)
    with jax.named_scope("head"):
        x = apply_norm(params["final_norm"], x, cfg.norm, cfg.norm_eps)
        logits = _logits(params, cfg, x, ctx)[:, 0]
    return new_cache, logits
