"""Plain reference forward of a dense GQA decoder, in ``jax.numpy``.

Follows the published Llama/Qwen2 block: RMSNorm, rotary embeddings
(rotate-half), grouped-query causal attention, SwiGLU MLP, pre-norm
residuals, tied output embedding; where the weights hold q/k/v biases
(Qwen2), they are added right after the projections, before the
rotary embeddings.  Float32 at ``highest`` matmul precision; the other
entries of :data:`NUMERICS` run the same equations at lower precision,
or without the biases, for the controls.  It imports nothing of the
program: the weights come from :func:`bench.model.make_params` with the
run's seed, and it runs one layer at a time, with queries in blocks, so
that a whole session's context fits beside the weights.
"""
from __future__ import annotations

import functools
from typing import Dict, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

Q_BLOCK = 512
ROW_BLOCK = 256

# name -> (activation and weight dtype, matmul precision, weights
# rounded to int8 with one scale per output channel, q/k/v biases left
# out)
NUMERICS = {
    "float32": (jnp.float32, "highest", False, False),  # the reference
    "float32_default": (jnp.float32, "default", False, False),
    "bfloat16": (jnp.bfloat16, "default", False, False),
    "int8": (jnp.bfloat16, "default", True, False),
    "float32_nobias": (jnp.float32, "highest", False, True),
}
BIASES = ("bq", "bk", "bv")


def _int8(w, axis):
    """``w`` rounded to int8 with one symmetric scale per slice along
    ``axis`` (the input axis), back in float32."""
    w = w.astype(jnp.float32)
    scale = jnp.max(jnp.abs(w), axis=axis, keepdims=True) / 127.0
    scale = jnp.where(scale > 0, scale, 1.0)
    return jnp.clip(jnp.round(w / scale), -127, 127) * scale


def _rms_norm(x, w, eps):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps)
    return (y * w.astype(jnp.float32)).astype(x.dtype)


def _rope(x, theta):
    """x: [T, heads, hd]; position of row t is t."""
    T, _, hd = x.shape
    half = hd // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(
        jnp.float32)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           -1).astype(x.dtype)


@functools.partial(jax.jit, static_argnames=("dims", "dtype", "prec"))
def _layer(x, lp, *, dims, dtype, prec):
    H, Hk, hd, eps, theta = dims
    T = x.shape[0]
    G = H // Hk
    mm = functools.partial(jnp.matmul, precision=prec)
    h = _rms_norm(x, lp["norm1"], eps)

    def proj(w, b):
        y = mm(h, lp[w])
        return y + lp[b] if b in lp else y
    q = _rope(proj("wq", "bq").reshape(T, H, hd), theta)
    k = _rope(proj("wk", "bk").reshape(T, Hk, hd), theta)
    v = proj("wv", "bv").reshape(T, Hk, hd)
    qb = q.reshape(T // Q_BLOCK, Q_BLOCK, Hk, G, hd)
    scale = 1.0 / np.sqrt(hd)

    def block(args):
        i, qi = args                                   # qi [Qb, Hk, G, hd]
        s = jnp.einsum("qkgd,tkd->kgqt", qi, k, precision=prec
                       ).astype(jnp.float32) * scale
        rows = i * Q_BLOCK + jnp.arange(Q_BLOCK)[:, None]
        s = jnp.where(jnp.arange(T)[None, :] <= rows, s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1).astype(dtype)
        return jnp.einsum("kgqt,tkd->qkgd", p, v, precision=prec)

    o = jax.lax.map(block, (jnp.arange(T // Q_BLOCK), qb))
    x = x + mm(o.reshape(T, H * hd).astype(dtype), lp["wo"])
    h = _rms_norm(x, lp["norm2"], eps)
    f = jax.nn.silu(mm(h, lp["w_gate"])) * mm(h, lp["w_up"])
    return x + mm(f, lp["w_down"])


@functools.partial(jax.jit, static_argnames=("prec",))
def _logit_stats(h, embed, targets, *, prec):
    """Per row: best logit, its token, and the logit of ``targets``."""
    logits = jnp.matmul(h, embed.T, precision=prec).astype(jnp.float32)
    best = jnp.max(logits, -1)
    arg = jnp.argmax(logits, -1).astype(jnp.int32)
    at = jnp.take_along_axis(logits, targets[:, None], -1)[:, 0]
    return best, arg, at


def _embed(params: Dict, numerics: str):
    dtype, _, int8, _ = NUMERICS[numerics]
    e = params["embed"]
    return (_int8(e, 1) if int8 else e).astype(dtype)


def _layer_params(params: Dict, i: int, numerics: str) -> Dict:
    """Layer ``i``'s weights at ``numerics``; biases are never rounded
    to int8."""
    dtype, _, int8, no_bias = NUMERICS[numerics]
    g = params["groups"]["l0"]

    def w(x):
        return (_int8(x[i], 0) if int8 else x[i]).astype(dtype)
    attn = {k: (x[i].astype(dtype) if k in BIASES else w(x))
            for k, x in g["attn"].items()
            if not (no_bias and k in BIASES)}
    return {"norm1": g["norm1"][i], "norm2": g["norm2"][i], **attn,
            **{k: w(x) for k, x in g["ffn"].items()}}


def final_hidden(params: Dict, cfg: Dict, tokens: np.ndarray,
                 pad_to: int, numerics: str = "float32") -> jax.Array:
    """Normalised last-layer states [pad_to, d] of ``tokens`` (padded at
    the end, which causal attention keeps out of the real rows)."""
    dtype, prec, _, _ = NUMERICS[numerics]
    T = len(tokens)
    pad = -(-max(pad_to, T) // Q_BLOCK) * Q_BLOCK
    toks = np.zeros((pad,), np.int32)
    toks[:T] = tokens
    x = _embed(params, numerics)[jnp.asarray(toks)]
    dims = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["head_dim"], float(cfg["rms_norm_eps"]),
            float(cfg["rope_theta"]))
    for i in range(cfg["num_hidden_layers"]):
        x = _layer(x, _layer_params(params, i, numerics), dims=dims,
                   dtype=jnp.dtype(dtype), prec=prec)
    return _rms_norm(x, params["final_norm"], float(cfg["rms_norm_eps"]))


def logit_stats(params: Dict, h: jax.Array, rows: Sequence[int],
                targets: Sequence[int], numerics: str = "float32",
                ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """(best, argmax, logit of target) at the given rows of ``h``."""
    dtype, prec, _, _ = NUMERICS[numerics]
    embed = _embed(params, numerics)
    rows, targets = np.asarray(rows, np.int32), np.asarray(targets,
                                                           np.int32)
    out = ([], [], [])
    for a in range(0, len(rows), ROW_BLOCK):
        r = np.zeros((ROW_BLOCK,), np.int32)
        t = np.zeros((ROW_BLOCK,), np.int32)
        n = len(rows[a:a + ROW_BLOCK])
        r[:n], t[:n] = rows[a:a + ROW_BLOCK], targets[a:a + ROW_BLOCK]
        res = _logit_stats(h[jnp.asarray(r)].astype(dtype), embed,
                           jnp.asarray(t), prec=prec)
        for acc, v in zip(out, res):
            acc.append(np.asarray(v)[:n])
    return tuple(np.concatenate(o) if o else np.zeros((0,))
                 for o in out)
