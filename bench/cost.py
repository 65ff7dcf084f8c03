"""Operations and bytes of the served work, from shapes and live lengths.

Counts are the model's own (what a dense GQA block needs), not what an
executable happens to run: padding, masked rows and the unused part of
a slot are not counted.  Weights and the KV cache are float32, the
precision the program serves.  ``cfg`` is a configuration dict
(``bench/configs/*.json``); with ``qkv_bias`` the q/k/v biases count,
their bytes once per step and one add per element per token.
"""
from __future__ import annotations

import json
from pathlib import Path
from typing import Dict, Iterable

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"
F32 = 4


def peaks(device_kind: str, path: Path = PEAKS_FILE) -> Dict[str, float]:
    """The chip's published peaks; an unknown chip is an error."""
    table = json.loads(Path(path).read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device_kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


def _dims(cfg: Dict):
    return (cfg["num_hidden_layers"], cfg["hidden_size"],
            cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["head_dim"], cfg["intermediate_size"], cfg["vocab_size"])


def _bias_width(cfg: Dict) -> int:
    """Elements of one layer's q/k/v biases (0 without them)."""
    _, _, H, Hk, hd, _, _ = _dims(cfg)
    return (H * hd + 2 * Hk * hd) if cfg.get("qkv_bias", False) else 0


def layer_matmul_flops(cfg: Dict) -> int:
    """One token through every layer's projections (their biases'
    adds included) and MLP."""
    L, d, H, Hk, hd, F, _ = _dims(cfg)
    return 2 * L * (d * H * hd + 2 * d * Hk * hd + H * hd * d + 3 * d * F) \
        + L * _bias_width(cfg)


def logits_flops(cfg: Dict) -> int:
    _, d, _, _, _, _, V = _dims(cfg)
    return 2 * d * V


def attention_flops(cfg: Dict, keys: int) -> int:
    """One query token attending to ``keys`` positions (QK^T and PV)."""
    L, _, H, _, hd, _, _ = _dims(cfg)
    return 4 * L * H * hd * keys


def decode_token_flops(cfg: Dict, keys: int) -> int:
    """One decoded token whose query attends to ``keys`` positions (its
    own included), logits over the vocabulary included."""
    return layer_matmul_flops(cfg) + attention_flops(cfg, keys) \
        + logits_flops(cfg)


def prefill_flops(cfg: Dict, tokens: int, context: int) -> int:
    """``tokens`` prompt tokens appended behind ``context`` cached ones,
    with logits at the last position only."""
    n, c = int(tokens), int(context)
    keys = n * c + n * (n + 1) // 2          # sum over i of (c + i)
    return n * layer_matmul_flops(cfg) + attention_flops(cfg, keys) \
        + logits_flops(cfg)


def param_bytes(cfg: Dict) -> int:
    L, d, H, Hk, hd, F, V = _dims(cfg)
    per_layer = 2 * d + d * (2 * H * hd + 2 * Hk * hd) + 3 * d * F \
        + _bias_width(cfg)
    return F32 * (L * per_layer + V * d + d)


def kv_row_bytes(cfg: Dict) -> int:
    """K and V of one position across every layer."""
    L, _, _, Hk, hd, _, _ = _dims(cfg)
    return F32 * 2 * L * Hk * hd


def decode_step_bytes(cfg: Dict, lane_keys: Iterable[int]) -> int:
    """One decode step over the active lanes: every weight read once,
    each lane's live K/V rows read, its new row written."""
    keys = list(lane_keys)
    return param_bytes(cfg) + kv_row_bytes(cfg) * (sum(keys) + len(keys))
