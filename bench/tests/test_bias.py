"""Q/K/V projection biases (Qwen2) in the seeded weights, the reference
and its control, on the CPU at toy widths: a configuration without them
draws today's weights bit for bit, the reference with them agrees with
an independent float64 NumPy forward, the reference with them left out
reads above the limit, and the program is asked for them by name and
given them exactly as the file says."""
from __future__ import annotations

import dataclasses
import hashlib
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import correctness, model, reference

FIX = Path(__file__).resolve().parent / "fixtures"
# sha256 of every leaf of tiny.json's weights at seed 2**32 + 1, in key
# order, as drawn before the biases were added (CPU)
UNBIASED_DIGEST = \
    "31f73ff9b51625c2ad7298f82e23ffc528a9ea1651b70de24f5d62bda0473989"


def _cfg(name):
    return json.loads((FIX / "configs" / f"{name}.json").read_text())


def _leaves(params):
    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    return {jax.tree_util.keystr(p): np.asarray(x) for p, x in flat}


def test_params_without_qkv_bias_are_the_earlier_draws():
    leaves = _leaves(model.make_params(_cfg("tiny"), 2 ** 32 + 1))
    h = hashlib.sha256()
    for k in sorted(leaves):
        h.update(leaves[k].tobytes())
    assert h.hexdigest() == UNBIASED_DIGEST


def test_biases_leave_every_other_weight_as_it_was():
    cfg = _cfg("tiny_bias")
    plain = _leaves(model.make_params({**cfg, "qkv_bias": False}, 7))
    biased = _leaves(model.make_params(cfg, 7))
    extra = sorted(set(biased) - set(plain))
    assert extra == [f"['groups']['l0']['attn']['{b}']"
                     for b in ("bk", "bq", "bv")]
    for k, x in plain.items():
        np.testing.assert_array_equal(biased[k], x)
    L, q, kv = 2, 4 * 16, 1 * 16
    for b, width in (("bq", q), ("bk", kv), ("bv", kv)):
        x = biased[f"['groups']['l0']['attn']['{b}']"]
        assert x.shape == (L, width)
        assert 0.5 * model.BIAS_SCALE < x.std() < 1.5 * model.BIAS_SCALE


def _numpy_logits(params, cfg, toks, biases=True):
    """Qwen2's block in float64 NumPy, written apart from the reference:
    the biases join the projections before the rotary embedding."""
    p = jax.tree_util.tree_map(lambda x: np.asarray(x, np.float64), params)
    g, a = p["groups"]["l0"], p["groups"]["l0"]["attn"]
    H, Hk, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    eps, T, half = cfg["rms_norm_eps"], len(toks), cfg["head_dim"] // 2
    ang = np.arange(T)[:, None] * cfg["rope_theta"] ** (
        -np.arange(half) / half)
    cos, sin = np.cos(ang)[:, None], np.sin(ang)[:, None]

    def rope(u):
        u1, u2 = u[..., :half], u[..., half:]
        return np.concatenate([u1 * cos - u2 * sin, u2 * cos + u1 * sin],
                              -1)

    def rms(u, w):
        return u / np.sqrt(np.mean(u * u, -1, keepdims=True) + eps) * w

    def proj(h, i, w, b, heads):
        y = h @ a[w][i] + (a[b][i] if biases else 0.0)
        return y.reshape(T, heads, hd)

    causal = np.tril(np.ones((T, T), bool))
    x = p["embed"][toks]
    for i in range(cfg["num_hidden_layers"]):
        h = rms(x, g["norm1"][i])
        q = rope(proj(h, i, "wq", "bq", H))
        k = np.repeat(rope(proj(h, i, "wk", "bk", Hk)), H // Hk, axis=1)
        v = np.repeat(proj(h, i, "wv", "bv", Hk), H // Hk, axis=1)
        s = np.einsum("qhd,khd->hqk", q, k) / np.sqrt(hd)
        s = np.where(causal, s, -np.inf)
        pr = np.exp(s - s.max(-1, keepdims=True))
        pr /= pr.sum(-1, keepdims=True)
        x = x + np.einsum("hqk,khd->qhd", pr, v).reshape(T, H * hd) \
            @ a["wo"][i]
        h = rms(x, g["norm2"][i])
        gate, up = h @ g["ffn"]["w_gate"][i], h @ g["ffn"]["w_up"][i]
        x = x + (gate / (1.0 + np.exp(-gate)) * up) @ g["ffn"]["w_down"][i]
    return rms(x, p["final_norm"]) @ p["embed"].T


def test_biased_reference_matches_a_float64_numpy_forward():
    cfg = _cfg("tiny_bias")
    params = model.make_params(cfg, 2 ** 32 + 5)
    toks = np.random.default_rng(2).integers(0, cfg["vocab_size"], 200)
    want = _numpy_logits(params, cfg, toks)
    h = reference.final_hidden(params, cfg, toks, pad_to=len(toks))
    got = np.asarray(jnp.matmul(h[:len(toks)], params["embed"].T,
                                precision="highest"))
    scale = np.abs(want).max()
    np.testing.assert_allclose(got, want, rtol=0, atol=1e-4 * scale)
    # the biases are not lost in the rounding: without them the same
    # forward departs by far more than that tolerance
    bare = _numpy_logits(params, cfg, toks, biases=False)
    assert np.abs(bare - want).max() > 100 * 1e-4 * scale


def test_dropped_bias_control_reads_above_the_limit():
    cfg = _cfg("tiny_bias")
    assert correctness.controls(cfg)[-1] == "float32_nobias"
    assert "float32_nobias" not in correctness.controls(_cfg("tiny"))
    params = model.make_params(cfg, 11)
    toks = np.random.default_rng(3).integers(0, cfg["vocab_size"], 300)
    rows = np.arange(99, 299, dtype=np.int32)
    smp = correctness.Sample(toks.astype(np.int32), rows, toks[rows + 1],
                             np.zeros_like(rows))
    limit = cfg["correct"]["max_logit_gap"]
    gaps = correctness.control_gaps(params, cfg, [smp], len(toks),
                                    "float32_nobias")
    assert correctness.gap_readings(gaps)["max_logit_gap"] > 100 * limit
    same = correctness.control_gaps(params, cfg, [smp], len(toks),
                                    "float32")
    assert correctness.gap_readings(same)["max_logit_gap"] <= limit


@dataclasses.dataclass(frozen=True)
class _Registry:
    """A dense registry entry as the program's ``get_config`` gives one,
    without a ``qkv_bias`` field."""
    name: str = "stand-in"
    family: str = "dense"
    num_layers: int = 36
    d_model: int = 2048
    num_heads: int = 16
    num_kv_heads: int = 2
    d_ff: int = 11008
    vocab_size: int = 151936
    head_dim: int = 128
    rope_theta: float = 1e6
    norm_eps: float = 1e-6
    tie_embeddings: bool = True
    kv_layout: str = "slab"


@dataclasses.dataclass(frozen=True)
class _RegistryWithBias(_Registry):
    """The same entry once the program serves the biases; the registry
    holds them by construction, as Qwen2's does."""
    qkv_bias: bool = True


# (the program has the field, what the file says: None is silent)
@pytest.mark.parametrize("has_field,says", [
    (False, True), (True, True), (True, None), (True, False),
    (False, False), (False, None)])
def test_model_config_asks_the_program_for_qkv_bias(monkeypatch, has_field,
                                                     says):
    from repro.configs import base
    entry = _RegistryWithBias() if has_field else _Registry()
    monkeypatch.setattr(base, "get_config", lambda arch: entry)
    cfg = {k: v for k, v in _cfg("tiny_bias").items() if k != "qkv_bias"}
    if says is not None:
        cfg["qkv_bias"] = says
    if says and not has_field:
        with pytest.raises(ValueError, match="no field 'qkv_bias'"):
            model.model_config(cfg)
        return
    got = model.model_config(cfg)
    # every size comes from the file, not from the registry
    assert (got.num_kv_heads, got.d_model, got.num_layers) == (1, 64, 2)
    if has_field:   # the file's value both ways; silent means no biases
        assert got.qkv_bias is bool(says)
    else:
        assert not hasattr(got, "qkv_bias")
