"""The plain reference agrees with the program's cache-free forward
(``models/model.py::forward_cold``) on seeded weights at toy widths,
on the CPU, in float32."""
from __future__ import annotations

import dataclasses
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import model, reference

FIX = Path(__file__).resolve().parent / "fixtures"


@pytest.mark.parametrize("overrides", [
    {},
    {"rope_theta": 1e6, "rms_norm_eps": 1e-6, "num_key_value_heads": 1},
])
def test_reference_matches_program_forward(overrides):
    from repro.models.model import forward_cold
    cfg = {**json.loads((FIX / "configs" / "tiny.json").read_text()),
           **overrides}
    params = model.make_params(cfg, 2 ** 32 + 1)
    mcfg = model.model_config(cfg)
    assert dataclasses.asdict(mcfg)["num_kv_heads"] == \
        cfg["num_key_value_heads"]
    toks = np.random.default_rng(0).integers(0, cfg["vocab_size"], 700)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(forward_cold(params, mcfg,
                                       jnp.asarray(toks[None]))[0])
    h = reference.final_hidden(params, cfg, toks, pad_to=len(toks))
    got = np.asarray(jnp.matmul(h[len(toks) - 1], params["embed"].T,
                                precision="highest"))
    np.testing.assert_allclose(got, want, rtol=1e-4,
                               atol=1e-4 * np.abs(want).max())
    best, arg, at = reference.logit_stats(params, h, [len(toks) - 1],
                                          [int(np.argmin(want))])
    assert arg[0] == int(np.argmax(want))
    np.testing.assert_allclose(best[0], want.max(), rtol=1e-4)
    np.testing.assert_allclose(at[0], want.min(), rtol=1e-4)


def _departure(numerics):
    cfg = json.loads((FIX / "configs" / "tiny.json").read_text())
    params = model.make_params(cfg, 5)
    toks = np.random.default_rng(1).integers(0, cfg["vocab_size"], 300)
    ref = reference.final_hidden(params, cfg, toks, pad_to=len(toks))
    got = reference.final_hidden(params, cfg, toks, pad_to=len(toks),
                                 numerics=numerics)
    return float(jnp.max(jnp.abs(ref[:300]
                                 - got[:300].astype(jnp.float32))))


def test_bfloat16_reference_departs_from_float32():
    assert 1e-3 < _departure("bfloat16") < 1.0


@pytest.mark.parametrize("numerics,lo,hi", [
    ("float32_default", 0.0, 1e-3),
    ("int8", 1e-3, 1.0),
])
def test_lower_numerics_depart_from_float32(numerics, lo, hi):
    """On the CPU, float32 at default precision is float32; int8
    weights depart from it."""
    assert lo <= _departure(numerics) < hi


def test_int8_weights_round_per_output_channel():
    w = jnp.asarray([[1.0, -0.02], [0.5, 0.01], [-0.25, 0.0]])
    q = np.asarray(reference._int8(w, 0))
    np.testing.assert_allclose(q[:, 0], [1.0, 0.5, -0.25], atol=1 / 254)
    np.testing.assert_allclose(q[:, 1], [-0.02, 0.01, 0.0],
                               atol=0.02 / 254)
    assert len(np.unique(np.round(np.asarray(q[:, 1]) / (0.02 / 127)))) \
        == 3
