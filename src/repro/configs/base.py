"""Config system: architecture + input-shape registries.

Every assigned architecture gets one module in this package defining a
``ModelConfig`` with the exact published dimensions (source cited in the
module docstring).  ``reduced()`` derives the CPU-smoke-test variant of
the same family (≤2 layers, d_model ≤ 512, ≤4 experts) as required by
the reproduction contract.
"""
from __future__ import annotations

import dataclasses
import importlib
import math
from typing import Optional, Tuple

# ---------------------------------------------------------------------------
# Input shapes (assigned; fixed by the reproduction contract)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class InputShape:
    name: str
    seq_len: int
    global_batch: int
    kind: str  # "train" | "prefill" | "decode"


INPUT_SHAPES = {
    "train_4k": InputShape("train_4k", 4_096, 256, "train"),
    "prefill_32k": InputShape("prefill_32k", 32_768, 32, "prefill"),
    "decode_32k": InputShape("decode_32k", 32_768, 128, "decode"),
    "long_500k": InputShape("long_500k", 524_288, 1, "decode"),
}


# ---------------------------------------------------------------------------
# Model config
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    num_experts: int
    top_k: int
    # Apply an MoE MLP every `every` layers (1 = every layer). Jamba uses 2.
    every: int = 1
    # Router auxiliary load-balance loss weight (train path).
    aux_loss_weight: float = 0.01


@dataclasses.dataclass(frozen=True)
class SSMConfig:
    """Mamba-2 (SSD) hyper-parameters [arXiv:2405.21060]."""

    d_state: int = 128
    d_conv: int = 4
    expand: int = 2
    head_dim: int = 64
    chunk_size: int = 256

    def num_heads(self, d_model: int) -> int:
        return (self.expand * d_model) // self.head_dim


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str  # dense | moe | ssm | hybrid | audio | vlm
    num_layers: int
    d_model: int
    num_heads: int          # 0 for attention-free architectures
    num_kv_heads: int
    d_ff: int               # 0 for attention-free (pure SSM) architectures
    vocab_size: int
    head_dim: int = 0       # 0 -> d_model // num_heads
    moe: Optional[MoEConfig] = None
    ssm: Optional[SSMConfig] = None
    # hybrid: within each group of `hybrid_period` layers, layer index
    # `hybrid_attn_index` is attention, the rest are Mamba-2 (Jamba 1:7).
    hybrid_period: int = 0
    hybrid_attn_index: int = 0
    sliding_window: int = 0      # 0 = full attention (mixtral: 4096)
    encoder_only: bool = False   # hubert: bidirectional, no decode phase
    rope_theta: float = 10_000.0
    mrope: bool = False          # qwen2-vl M-RoPE (3 rotary sections)
    mrope_sections: Tuple[int, int, int] = (16, 24, 24)
    frontend: str = "none"       # none | audio | vision  (sanctioned stubs)
    # Serving prefill/resume attention path (DESIGN.md §4): "xla" = the
    # pure-JAX blocked scan (reference; streams all max_seq KV tiles per
    # chunk); "pallas" = the cache-aware Pallas kernel with scalar-
    # prefetched length/offset tile pruning (interpret-mode on CPU).
    prefill_kernel: str = "xla"
    # Serving decode attention path under the paged layout: "xla" =
    # gather pages then run the blocked reference; "pallas" = the
    # block-table flash-decode kernel (DESIGN.md §8).
    decode_kernel: str = "xla"
    # Serving KV-cache layout (DESIGN.md §8): "slab" = per-slot
    # contiguous [num_slots, max_seq] stripes (reference / parity
    # oracle); "paged" = flat page arena [num_pages, page_size] with
    # per-session block tables, refcounted page sharing and COW.
    kv_layout: str = "slab"
    kv_page_size: int = 64       # paged layout: tokens per page (= the
    #                              kernels' block_k tile)
    tie_embeddings: bool = False
    norm_eps: float = 1e-5
    act: str = "swiglu"          # swiglu | gelu
    source: str = ""             # citation

    # ---- derived -----------------------------------------------------
    def __post_init__(self):
        if self.head_dim == 0 and self.num_heads > 0:
            object.__setattr__(self, "head_dim", self.d_model // self.num_heads)

    @property
    def padded_vocab(self) -> int:
        """Embedding-table rows: vocab rounded up to a multiple of 128 so
        the vocab dim always divides the 16-wide model axis (and TPU
        lanes).  Logits are sliced back to ``vocab_size``."""
        return -(-self.vocab_size // 128) * 128

    @property
    def is_attention_free(self) -> bool:
        return self.family == "ssm"

    @property
    def has_decode_phase(self) -> bool:
        return not self.encoder_only

    def layer_kind(self, i: int) -> str:
        """'attn' or 'ssm' for layer i (hybrid interleave)."""
        if self.family == "ssm":
            return "ssm"
        if self.hybrid_period:
            return "attn" if (i % self.hybrid_period) == self.hybrid_attn_index else "ssm"
        return "attn"

    def layer_has_moe(self, i: int) -> bool:
        return self.moe is not None and (i % self.moe.every) == (self.moe.every - 1)

    def supports_shape(self, shape_name: str) -> bool:
        """Contract: encoder-only skips decode; long_500k needs sub-quadratic
        (native SSM/hybrid/SWA, or the sanctioned SWA decode variant for
        dense archs — which we do implement, so dense archs run it)."""
        s = INPUT_SHAPES[shape_name]
        if s.kind == "decode" and not self.has_decode_phase:
            return False
        return True

    def attention_window_for(self, shape_name: str) -> int:
        """Effective attention window for a shape. long_500k on archs with
        no native sub-quadratic path uses the sliding-window variant."""
        if self.sliding_window:
            return self.sliding_window
        if shape_name == "long_500k" and self.family not in ("ssm", "hybrid"):
            return 8_192  # sanctioned SWA decode variant (DESIGN.md §5)
        return 0

    # ---- parameter counting (for roofline MODEL_FLOPS) ---------------
    def param_count(self, active_only: bool = False) -> int:
        d, L = self.d_model, self.num_layers
        total = self.vocab_size * d  # embed
        if not self.tie_embeddings:
            total += self.vocab_size * d  # lm head / encoder proj
        for i in range(L):
            total += 2 * d  # norms
            if self.layer_kind(i) == "attn":
                hd = self.head_dim
                q = d * self.num_heads * hd
                kv = 2 * d * self.num_kv_heads * hd
                o = self.num_heads * hd * d
                total += q + kv + o
            else:
                ssm = self.ssm or SSMConfig()
                d_in = ssm.expand * d
                nh = ssm.num_heads(d)
                # in_proj produces [z, x, B, C, dt]
                total += d * (2 * d_in + 2 * ssm.d_state + nh)
                total += ssm.d_conv * (d_in + 2 * ssm.d_state)  # conv1d
                total += nh * 2  # A_log, D
                total += d_in * d  # out_proj
            if self.d_ff:
                n_mat = 3 if self.act == "swiglu" else 2
                ff = n_mat * d * self.d_ff
                if self.layer_has_moe(i):
                    m = self.moe
                    total += d * m.num_experts  # router
                    k = m.top_k if active_only else m.num_experts
                    total += k * ff
                else:
                    total += ff
        return total


# ---------------------------------------------------------------------------
# Reduced (smoke) variants
# ---------------------------------------------------------------------------


SMOKE_SUFFIX = "-smoke"   # registry name of an arch's reduced variant


def reduced(cfg: ModelConfig) -> ModelConfig:
    """Same family, shrunk per contract: ≤2 layers, d_model ≤ 512, ≤4 experts."""
    d_model = min(cfg.d_model, 256)
    heads = 4 if cfg.num_heads else 0
    kv = min(max(1, cfg.num_kv_heads * 4 // max(cfg.num_heads, 1) or 1), heads) if heads else 0
    kv = kv if heads == 0 or heads % kv == 0 else 2
    period = cfg.hybrid_period
    layers = 2 if not period else period  # hybrid smoke keeps one full group
    moe = None
    if cfg.moe:
        moe = MoEConfig(num_experts=4, top_k=min(2, cfg.moe.top_k),
                        every=min(cfg.moe.every, 2),
                        aux_loss_weight=cfg.moe.aux_loss_weight)
    ssm = None
    if cfg.ssm:
        ssm = SSMConfig(d_state=16, d_conv=4, expand=2, head_dim=32, chunk_size=32)
    return dataclasses.replace(
        cfg,
        name=cfg.name + SMOKE_SUFFIX,
        num_layers=layers,
        d_model=d_model,
        num_heads=heads,
        num_kv_heads=kv,
        head_dim=(d_model // heads) if heads else 0,
        d_ff=min(cfg.d_ff, 512) if cfg.d_ff else 0,
        vocab_size=min(cfg.vocab_size, 512),
        moe=moe,
        ssm=ssm,
        sliding_window=min(cfg.sliding_window, 64) if cfg.sliding_window else 0,
        mrope_sections=(8, 12, 12) if cfg.mrope else cfg.mrope_sections,
    )


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------

# assigned pool + the paper's own evaluation models
ARCH_MODULES = {
    "mixtral-8x22b": "mixtral_8x22b",
    "starcoder2-15b": "starcoder2_15b",
    "hubert-xlarge": "hubert_xlarge",
    "jamba-1.5-large-398b": "jamba_1_5_large",
    "mamba2-780m": "mamba2_780m",
    "olmoe-1b-7b": "olmoe_1b_7b",
    "qwen2-vl-7b": "qwen2_vl_7b",
    "smollm-360m": "smollm_360m",
    "llama3.2-3b": "llama3_2_3b",
    "phi4-mini-3.8b": "phi4_mini_3_8b",
    # paper's own testbed models (§IV-A)
    "qwen2.5-3b": "qwen2_5_3b",
    "qwen2.5-7b": "qwen2_5_7b",
    "llama3-8b": "llama3_8b",
}

ASSIGNED_ARCHS = list(ARCH_MODULES)[:10]


def get_config(name: str) -> ModelConfig:
    """The published config of ``name``; ``<arch>-smoke`` names its
    ``reduced()`` variant (the CPU-test size)."""
    arch = name.removesuffix(SMOKE_SUFFIX)
    if arch not in ARCH_MODULES:
        raise KeyError(f"unknown arch {name!r}; known: {sorted(ARCH_MODULES)}"
                       f" (each also as <arch>{SMOKE_SUFFIX})")
    cfg = importlib.import_module(f"repro.configs.{ARCH_MODULES[arch]}").CONFIG
    return reduced(cfg) if arch != name else cfg


def get_smoke_config(name: str) -> ModelConfig:
    return get_config(name + SMOKE_SUFFIX)


def all_configs():
    return {n: get_config(n) for n in ARCH_MODULES}
