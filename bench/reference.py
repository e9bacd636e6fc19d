"""Plain float32 reference of the served model, and the weights it shares
with the program.

It imports nothing of the program. The weights are made here, from the
seed, in the layout the program's `init_params` gives (checked by a
test), and handed to the program; the reference reads the same values
and computes in float32 at `highest` matmul precision. What depends on
the architecture (the parameter layout, the forward pass) is in
`bench/arch/<arch>.py`, named by the configuration's `"arch"` key.

`precision="int8"` or `"fp8"` computes the same forward with every
projection's weights (per output channel) and inputs (per token)
rounded to that format: the lower-precision control that the
correctness check has to reject.

`compare` holds the program's logits at each served position against
the reference's, a block of rows and of the vocabulary at a time.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench import arch

F32 = jnp.float32
Q_BLOCK = 512          # query rows per attention block
VOCAB_BLOCK = 16384    # output-head columns per block
ROW_BLOCK = 256        # hidden rows per output-head call


# --------------------------------------------------------------- weights
def init_params(seed: int, cfg: dict, dtype=jnp.bfloat16, device=None):
    """All weights from `seed` in one jitted call, made on `device` in
    `dtype`: each leaf of the architecture's layout is drawn and cast in
    one fused pass."""
    mod = arch.of(cfg)
    shapes = mod.param_shapes(cfg)
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
                    1.0 / np.sqrt(mod.fan_in(name, shape))
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


# --------------------------------------------------------------- forward
def bucket(n: int) -> int:
    """Padded sequence length: a power of two, at least one query
    block, so each length compiles once."""
    return max(Q_BLOCK, 1 << max(0, n - 1).bit_length())


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
    mod = arch.of(cfg)
    table = mod.output_table(params)
    h = jnp.pad(mod.hidden(params, cfg, seq)[pick], ((0, pad), (0, 0)))
    prog = np.pad(np.asarray(program_rows), ((0, pad), (0, 0)))
    tok = np.pad(np.stack([served, (served + 1) % cfg["vocab_size"]], -1),
                 ((0, pad), (0, 0)))
    hc = {c: jnp.pad(mod.hidden(params, cfg, seq, c)[pick],
                     ((0, pad), (0, 0))) for c in controls}
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
