"""Model configurations and seeded weights.

A configuration is a JSON file ``bench/configs/<name>.json``: the sizes
of the published ``config.json`` under their own keys, the program's
registry entry it is served through (``arch``), the engine's shapes
(``engine``), and ``source``, ``reduced`` and ``assumed``.

Weights are the benchmark's, not the program's: one jitted call makes
them on the device from ``--seed``, in the layout the program's model
takes, and :mod:`bench.reference` rebuilds the same arrays from the
same seed for its own forward pass.

``"qkv_bias": true`` (absent means false; Qwen2's attention has the
biases by construction, so its ``config.json`` names no such key) adds
biases to the q, k and v projections: ``bq`` ``[L, H * hd]``, ``bk``
and ``bv`` ``[L, Hk * hd]`` under ``groups.l0.attn``, drawn at
:data:`BIAS_SCALE` from a key of their own, so a file without the key
gets the same weights as before, bit for bit.  The program takes them
as ``ModelConfig.qkv_bias``, set from the file both ways wherever the
program has that field.
"""
from __future__ import annotations

import dataclasses
import functools
import json
from pathlib import Path
from typing import Dict

import jax
import jax.numpy as jnp

CONFIG_DIR = Path(__file__).resolve().parent / "configs"
# standard deviation of the seeded q/k/v biases: large enough that the
# reference with the biases left out reads far above a configuration's
# ``max_logit_gap`` limit (PERF.md gives the readings)
BIAS_SCALE = 1.0

# published config.json key -> the program's ModelConfig field
FIELDS = {
    "num_hidden_layers": "num_layers",
    "hidden_size": "d_model",
    "num_attention_heads": "num_heads",
    "num_key_value_heads": "num_kv_heads",
    "head_dim": "head_dim",
    "intermediate_size": "d_ff",
    "vocab_size": "vocab_size",
    "rope_theta": "rope_theta",
    "rms_norm_eps": "norm_eps",
    "tie_word_embeddings": "tie_embeddings",
}


def load_config(name: str, config_dir: Path = CONFIG_DIR) -> Dict:
    path = Path(config_dir) / f"{name}.json"
    if not path.is_file():
        known = sorted(p.stem for p in Path(config_dir).glob("*.json"))
        raise KeyError(f"unknown configuration {name!r}; known: {known}")
    cfg = json.loads(path.read_text())
    missing = [k for k in (*FIELDS, "arch", "engine") if k not in cfg]
    if missing:
        raise ValueError(f"{path}: missing keys {missing}")
    if cfg.get("hidden_act", "silu") != "silu":
        raise ValueError(f"{path}: only SwiGLU (silu) blocks are served")
    return cfg


def model_config(cfg: Dict):
    """The program's ``ModelConfig``: its registry entry with every size
    set from the file, so what is served is what the file says.  So is
    ``qkv_bias`` (absent means false) where the program has the field;
    where it has none, a file that asks for the biases is refused."""
    from repro.configs.base import get_config
    base = get_config(cfg["arch"])
    if base.family != "dense":
        raise ValueError(f"{cfg['arch']}: only dense GQA models here")
    over = {f: cfg[k] for k, f in FIELDS.items()}
    over["rope_theta"] = float(over["rope_theta"])
    over["norm_eps"] = float(over["norm_eps"])
    over["kv_layout"] = cfg["engine"].get("kv_layout", "slab")
    bias = bool(cfg.get("qkv_bias", False))
    if "qkv_bias" in {f.name for f in dataclasses.fields(base)}:
        over["qkv_bias"] = bias     # the file's, whatever the registry's
    elif bias:
        raise ValueError(
            f"{cfg['arch']}: the configuration sets qkv_bias, and the "
            f"program's ModelConfig has no field 'qkv_bias' to serve "
            f"the q/k/v projection biases (bq, bk, bv) with")
    return dataclasses.replace(base, **over)


def seed_key(seed: int):
    """A PRNG key from any whole number (``--seed`` may pass 2**32)."""
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)


def _shapes(cfg: Dict) -> Dict:
    L, d = cfg["num_hidden_layers"], cfg["hidden_size"]
    H, Hk = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd, F, V = cfg["head_dim"], cfg["intermediate_size"], cfg["vocab_size"]
    return {"L": L, "d": d, "q": H * hd, "kv": Hk * hd, "F": F, "V": V}


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _make(key, dims: tuple, dtype, qkv_bias: bool):
    s = dict(dims)
    L, d, q, kv, F, V = s["L"], s["d"], s["q"], s["kv"], s["F"], s["V"]
    ks = iter(jax.random.split(key, 12))

    def dense(k, fan_in, shape):
        return (jax.random.normal(k, shape, jnp.float32)
                / jnp.sqrt(jnp.float32(fan_in))).astype(dtype)

    def norm(k, shape):
        return (1.0 + 0.1 * jax.random.normal(k, shape, jnp.float32)
                ).astype(dtype)

    layer = {
        "norm1": norm(next(ks), (L, d)),
        "attn": {"wq": dense(next(ks), d, (L, d, q)),
                 "wk": dense(next(ks), d, (L, d, kv)),
                 "wv": dense(next(ks), d, (L, d, kv)),
                 "wo": dense(next(ks), q, (L, q, d))},
        "norm2": norm(next(ks), (L, d)),
        "ffn": {"w_gate": dense(next(ks), d, (L, d, F)),
                "w_up": dense(next(ks), d, (L, d, F)),
                "w_down": dense(next(ks), F, (L, F, d))},
    }
    if qkv_bias:        # a key apart from the 12 above: they stay as drawn
        kb = jax.random.split(jax.random.fold_in(key, 0x62696173), 3)
        for k, name, width in zip(kb, ("bq", "bk", "bv"), (q, kv, kv)):
            layer["attn"][name] = (BIAS_SCALE * jax.random.normal(
                k, (L, width), jnp.float32)).astype(dtype)
    return {"embed": (0.02 * jax.random.normal(next(ks), (V, d),
                                               jnp.float32)).astype(dtype),
            "groups": {"l0": layer},
            "final_norm": norm(next(ks), (d,))}


def make_params(cfg: Dict, seed: int, dtype=jnp.float32) -> Dict:
    """Every weight, on the device, in one call: the program's layout
    (``models/model.py``: one layer per scanned group, tied
    embeddings; ``embed`` rows = vocab, a multiple of 128 here)."""
    if not cfg["tie_word_embeddings"]:
        raise ValueError("untied output heads are not generated here")
    if cfg["vocab_size"] % 128:
        raise ValueError("vocab must be a multiple of 128 (the program "
                         "pads the embedding table to one)")
    dims = tuple(sorted(_shapes(cfg).items()))
    return _make(seed_key(seed), dims, jnp.dtype(dtype),
                 bool(cfg.get("qkv_bias", False)))

