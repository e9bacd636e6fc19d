"""Plain float32 reference of the served decoder, and the weights it
shares with the program.

It imports nothing of the program. The weights are made here, from the
seed, in the layout the program's `init_params` gives (checked by a
test), and handed to the program; the reference reads the same bf16
values and computes in float32 at `highest` matmul precision, one layer
at a time and attention in blocks of queries, so it fits beside the
weights on one chip.

`precision="int8"` or `"fp8"` computes the same forward with every
projection's weights (per output channel) and inputs (per token)
rounded to that format: the lower-precision control that the
correctness check has to reject.

`compare` holds the program's logits at each served position against
the reference's, a block of rows and of the vocabulary at a time.

The architecture (as in each configuration's `architecture` line): token
embedding; per layer x += Attn(RMSNorm(x)), x += SwiGLU(RMSNorm(x));
final RMSNorm; untied output head. Attention: q/k/v projections, RoPE on
q and k with rotate-half pairs and inverse frequencies
theta^(-2i/head_dim), causal softmax(q k^T / sqrt(head_dim)) v with each
group of n_heads / n_kv query heads sharing one K/V head, output
projection. SwiGLU: w_out(silu(x w_gate) * (x w_in)).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
Q_BLOCK = 512          # query rows per attention block
VOCAB_BLOCK = 16384    # output-head columns per block
ROW_BLOCK = 256        # hidden rows per output-head call


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


def _fan_in(path: str, shape) -> int:
    if path.endswith("wo"):
        return shape[1] * shape[2]
    return shape[1]


def init_params(seed: int, cfg: dict, dtype=jnp.bfloat16, device=None):
    """All weights from `seed` in one jitted call, made on `device` in
    `dtype`: each leaf is drawn and cast in one fused pass."""
    shapes = param_shapes(cfg)
    flat, treedef = jax.tree.flatten_with_path(
        shapes, is_leaf=lambda s: isinstance(s, tuple)
        and all(isinstance(e, int) for e in s))
    names = [jax.tree_util.keystr(p) for p, _ in flat]

    def init(key):
        keys = jax.random.split(key, len(flat))
        out = []
        for k, name, (_, shape) in zip(keys, names, flat):
            if "norm" in name:
                a = jax.random.uniform(k, shape, F32, 0.5, 1.5)
            else:
                std = 0.02 if "embed" in name else \
                    1.0 / np.sqrt(_fan_in(name, shape))
                a = jax.random.truncated_normal(k, -2.0, 2.0, shape,
                                                F32) * std
            out.append(a.astype(dtype))
        return jax.tree.unflatten(treedef, out)

    shard = None if device is None else \
        jax.sharding.SingleDeviceSharding(device)
    return jax.jit(init, out_shardings=shard)(
        jax.random.PRNGKey(seed % (2**31 - 1)))


# ------------------------------------------------------------- precision
def _round(a, axes, precision: str):
    """`a` rounded to `precision` with one scale per slice over `axes`
    (the contraction axes), returned in float32."""
    if precision == "f32":
        return a
    amax = jnp.max(jnp.abs(a), axis=axes, keepdims=True)
    if precision == "int8":
        s = jnp.maximum(amax, 1e-30) / 127.0
        return jnp.clip(jnp.round(a / s), -127, 127) * s
    if precision == "fp8":
        s = jnp.maximum(amax, 1e-30) / 448.0
        return (a / s).astype(jnp.float8_e4m3fn).astype(F32) * s
    raise ValueError(f"unknown precision {precision!r}")


def _mm(spec: str, x, w, precision: str, x_axes, w_axes):
    return jnp.einsum(spec, _round(x, x_axes, precision),
                      _round(w.astype(F32), w_axes, precision))


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale.astype(F32)


def _rope(x, theta):
    """x [P, heads, hd] at positions 0..P-1."""
    p, _, hd = x.shape
    half = hd // 2
    inv = theta ** (-jnp.arange(half, dtype=F32) * 2.0 / hd)
    ang = jnp.arange(p, dtype=F32)[:, None] * inv[None]
    sin, cos = jnp.sin(ang)[:, None], jnp.cos(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


# --------------------------------------------------------------- forward
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


def bucket(n: int) -> int:
    """Padded sequence length: a power of two, at least one query
    block, so each length compiles once."""
    return max(Q_BLOCK, 1 << max(0, n - 1).bit_length())


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


@functools.partial(jax.jit, static_argnames=("precision",))
def _logits(rows, table, precision):
    """rows [R, d] against the whole output table at `precision`:
    logits [R, V] in float32, one vocab block at a time."""
    xr = _round(rows, (1,), precision)
    return jnp.concatenate(
        [xr @ _round(table[lo:lo + VOCAB_BLOCK].astype(F32), (1,),
                     precision).T
         for lo in range(0, table.shape[0], VOCAB_BLOCK)], axis=-1)


@jax.jit
def _against(rows, table, other, tokens):
    """The reference's logits at rows [R, d] against `other` [R, V]:
    (best [R], logits at `tokens` [R, K], squared error [R], squared
    norm [R])."""
    ref = _logits(rows, table, "f32")
    d = other.astype(F32) - ref
    return (ref.max(-1), jnp.take_along_axis(ref, tokens, axis=-1),
            (d * d).sum(-1), (ref * ref).sum(-1))


def _summary(best, at, err2, ref2) -> dict:
    return {"logit_err": float(np.sqrt(err2.sum() / ref2.sum())),
            "token_gap": float((best - at[:, 0]).max())}


def compare(params, cfg: dict, prompt, served, program_rows,
            controls=()) -> dict:
    """The float32 reference over the prompt and the served tokens,
    against the program's logits at each served position
    (`program_rows` [n, V], the row that chose each served token).

    Returns {"program": {...}, <control>: {...}} with, for each,
    `logit_err` (the relative L2 error of its logits over the request)
    and `token_gap` (the widest gap by which its chosen token's
    reference logit lies below the reference's best). For the program
    the chosen tokens are the served ones; a control (the reference
    computed in a lower precision, put in the program's place) chooses
    its own best at each position. The program's entry also holds
    `altered_gap`: the widest gap of token id + 1 at every seventh
    served position, what a token altered where it is produced would
    read."""
    prompt = np.asarray(prompt, np.int32)
    served = np.asarray(served, np.int32)
    s, n = len(prompt), len(served)
    seq = np.concatenate([prompt, served[:-1]])
    pick = slice(s - 1, s - 1 + n)
    pad = -n % ROW_BLOCK
    table = params["unembed"]
    h = jnp.pad(hidden(params, cfg, seq)[pick], ((0, pad), (0, 0)))
    prog = np.pad(np.asarray(program_rows), ((0, pad), (0, 0)))
    tok = np.pad(np.stack([served, (served + 1) % cfg["vocab_size"]], -1),
                 ((0, pad), (0, 0)))
    hc = {c: jnp.pad(hidden(params, cfg, seq, c)[pick], ((0, pad), (0, 0)))
          for c in controls}
    parts = {k: [] for k in ("program", *controls)}
    with jax.default_matmul_precision("highest"):
        for r0 in range(0, n + pad, ROW_BLOCK):
            rows = jax.lax.dynamic_slice_in_dim(h, r0, ROW_BLOCK)
            parts["program"].append(_against(
                rows, table, jnp.asarray(prog[r0:r0 + ROW_BLOCK]),
                jnp.asarray(tok[r0:r0 + ROW_BLOCK])))
            for c in controls:
                other = _logits(jax.lax.dynamic_slice_in_dim(
                    hc[c], r0, ROW_BLOCK), table, c)
                parts[c].append(_against(
                    rows, table, other,
                    jnp.argmax(other, -1).astype(jnp.int32)[:, None]))
    out = {}
    for k, got in parts.items():
        best, at, err2, ref2 = (
            np.concatenate([np.asarray(g[i], np.float64) for g in got])[:n]
            for i in range(4))
        out[k] = _summary(best, at, err2, ref2)
        if k == "program":
            every7 = np.arange(6, n, 7)
            out[k]["altered_gap"] = float(
                (best[every7] - at[every7, 1]).max()) if len(every7) \
                else None
    return out
