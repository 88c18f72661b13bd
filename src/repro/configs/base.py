"""Model / shape configuration system.

Every assigned architecture is expressed as a :class:`ModelConfig`.  The
pipeline unit is a *block*, one layer, so that stage boundaries can be
dynamic runtime arguments that fall between any two layers (see
DESIGN.md §2/§4).
"""
from __future__ import annotations

import dataclasses
import importlib
from typing import Optional, Tuple

# ---------------------------------------------------------------------------
# Sub-configs
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    """Mixture-of-experts settings for the FFN sublayer."""

    num_experts: int
    num_experts_per_tok: int
    d_expert: int                 # hidden size of each routed expert
    num_shared_experts: int = 0   # DeepSeek-style always-on experts
    d_shared: int = 0             # hidden size of each shared expert
    capacity_factor: float = 1.25
    router_aux_coef: float = 0.01
    # Apply MoE every `every` blocks starting at `offset` (Jamba: every=2).
    every: int = 1
    offset: int = 0


@dataclasses.dataclass(frozen=True)
class SSMConfig:
    """Mamba2 (SSD) settings."""

    d_state: int = 128
    d_conv: int = 4
    expand: int = 2
    head_dim: int = 64            # SSD head dim (P)
    chunk_size: int = 256

    def d_inner(self, d_model: int) -> int:
        return self.expand * d_model

    def num_heads(self, d_model: int) -> int:
        return self.d_inner(d_model) // self.head_dim


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    """Architecture description.

    ``family`` ∈ {dense, moe, ssm, hybrid, vlm, audio}.  ``layer_pattern``
    is the period of the layers' mixer kinds, drawn from {"attn",
    "mamba"}: layer ``i`` has kind ``layer_pattern[i % len]``.
    dense/moe/vlm/audio have ("attn",); the Jamba period is ("attn",) +
    ("mamba",)*7.  Every layer is one block, the pipeline unit.

    The multipliers of µP-style models (Granite-4.0-H) are ``None``
    where a model has none, and then add no operation:
    ``embedding_multiplier`` scales the embedded tokens,
    ``residual_multiplier`` each mixer and FFN branch before it is
    added, and the logits are divided by ``logits_scaling``.
    ``attention_scale`` replaces ``head_dim ** -0.5``; ``rope`` False
    is attention without position embedding (NoPE).
    """

    name: str
    family: str
    num_layers: int               # total sublayers, == num_blocks*len(pattern)
    d_model: int
    num_heads: int
    num_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: Optional[int] = None          # defaults to d_model // num_heads
    layer_pattern: Tuple[str, ...] = ("attn",)
    moe: Optional[MoEConfig] = None
    ssm: Optional[SSMConfig] = None
    # attention options
    qk_norm: bool = False
    qkv_bias: bool = False
    sliding_window: Optional[int] = None    # None = full attention
    causal: bool = True                     # False for encoder-only (audio)
    rope_theta: float = 1e6
    rope: bool = True
    attention_scale: Optional[float] = None  # None = head_dim ** -0.5
    rms_eps: float = 1e-6
    embedding_multiplier: Optional[float] = None
    residual_multiplier: Optional[float] = None
    logits_scaling: Optional[float] = None
    # modality frontend stub: inputs are precomputed embeddings, not tokens
    embedding_inputs: bool = False
    # has an autoregressive decode step at all
    is_decoder: bool = True
    # provenance
    source: str = ""

    # -- derived -----------------------------------------------------------
    def __post_init__(self):
        if self.head_dim is None:
            object.__setattr__(self, "head_dim", self.d_model // self.num_heads)
        if self.num_layers % len(self.layer_pattern) != 0:
            raise ValueError(
                f"{self.name}: num_layers={self.num_layers} not divisible by "
                f"pattern length {len(self.layer_pattern)}")

    @property
    def num_blocks(self) -> int:
        """Pipeline units: one per layer."""
        return self.num_layers

    def layer_kind(self, layer: int) -> str:
        """Mixer kind of layer ``layer``: "attn" or "mamba"."""
        return self.layer_pattern[layer % len(self.layer_pattern)]

    def sublayer_is_moe(self, layer: int) -> bool:
        """Whether the FFN of layer ``layer`` is MoE."""
        if self.moe is None:
            return False
        return layer % self.moe.every == self.moe.offset

    # Rough parameter counts (used for roofline MODEL_FLOPS and reports).
    def param_count(self) -> int:
        d, h = self.d_model, self.head_dim
        n_q, n_kv = self.num_heads, self.num_kv_heads
        total = self.vocab_size * d  # embed
        if self.is_decoder:
            total += self.vocab_size * d  # unembed (untied)
        for i in range(self.num_layers):
            if self.layer_kind(i) == "attn":
                total += d * (n_q * h) + 2 * d * (n_kv * h) + (n_q * h) * d
            else:  # mamba2
                s = self.ssm
                din = s.d_inner(d)
                nh = s.num_heads(d)
                # in_proj produces [z, x, B, C, dt]
                total += d * (2 * din + 2 * s.d_state + nh) + din * d
                total += s.d_conv * (din + 2 * s.d_state)
            total += 2 * d  # norms
            # FFN
            if self.moe is not None and self.sublayer_is_moe(i):
                m = self.moe
                total += m.num_experts * 3 * d * m.d_expert
                total += m.num_shared_experts * 3 * d * m.d_shared
                total += d * m.num_experts  # router
            elif self.d_ff > 0 and self.family != "ssm":
                total += 3 * d * self.d_ff
        return total

    def active_param_count(self) -> int:
        """Params touched per token (MoE: only routed top-k + shared)."""
        if self.moe is None:
            return self.param_count()
        d = self.d_model
        m = self.moe
        full = self.param_count()
        moe_layers = sum(
            1 for i in range(self.num_layers) if self.sublayer_is_moe(i))
        inactive = (m.num_experts - m.num_experts_per_tok) * 3 * d * m.d_expert
        return full - moe_layers * inactive


# ---------------------------------------------------------------------------
# Input shapes (assigned)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class InputShape:
    name: str
    seq_len: int
    global_batch: int
    mode: str  # "train" | "prefill" | "decode"


INPUT_SHAPES = {
    "train_4k": InputShape("train_4k", 4096, 256, "train"),
    "prefill_32k": InputShape("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": InputShape("decode_32k", 32768, 128, "decode"),
    "long_500k": InputShape("long_500k", 524288, 1, "decode"),
}


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------

_ARCH_MODULES = {
    "jamba-1.5-large-398b": "jamba_1_5_large_398b",
    "deepseek-moe-16b": "deepseek_moe_16b",
    "mixtral-8x22b": "mixtral_8x22b",
    "llava-next-34b": "llava_next_34b",
    "mamba2-370m": "mamba2_370m",
    "hubert-xlarge": "hubert_xlarge",
    "qwen3-32b": "qwen3_32b",
    "qwen3-4b": "qwen3_4b",
    "qwen2-0.5b": "qwen2_0_5b",
    "qwen3-8b": "qwen3_8b",
    "granite-4.0-h-micro": "granite_4_0_h_micro",
}

ARCH_IDS = tuple(_ARCH_MODULES)


def get_config(arch: str) -> ModelConfig:
    """Load the full (assigned) config for ``--arch <id>``."""
    if arch not in _ARCH_MODULES:
        raise KeyError(f"unknown arch {arch!r}; known: {sorted(_ARCH_MODULES)}")
    mod = importlib.import_module(f"repro.configs.{_ARCH_MODULES[arch]}")
    return mod.CONFIG


def get_smoke_config(arch: str) -> ModelConfig:
    """Reduced variant of the same family: ≤2 blocks, d_model ≤ 512,
    ≤4 experts."""
    if arch not in _ARCH_MODULES:
        raise KeyError(f"unknown arch {arch!r}; known: {sorted(_ARCH_MODULES)}")
    mod = importlib.import_module(f"repro.configs.{_ARCH_MODULES[arch]}")
    return mod.smoke_config()


def shape_is_applicable(cfg: ModelConfig, shape: InputShape) -> Tuple[bool, str]:
    """Whether (arch, shape) runs; returns (applicable, reason_if_not).

    See DESIGN.md §4 "Shape skips".
    """
    if shape.mode == "decode" and not cfg.is_decoder:
        return False, f"{cfg.name} is encoder-only: no decode step"
    if shape.name == "long_500k":
        subquadratic = (
            cfg.family in ("ssm", "hybrid")
            or cfg.sliding_window is not None)
        if not subquadratic:
            return False, (f"{cfg.name} is pure full-attention; long_500k "
                           "requires the sliding-window variant "
                           "(use long_context_variant())")
    return True, ""


def long_context_variant(cfg: ModelConfig, window: int = 8192) -> ModelConfig:
    """Sliding-window variant of a dense arch for long_500k (DESIGN.md §4)."""
    if cfg.family in ("ssm",):
        return cfg
    if cfg.sliding_window is not None:
        return cfg
    return dataclasses.replace(cfg, sliding_window=window,
                               name=cfg.name + "-swa")
