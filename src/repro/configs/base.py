"""Model configuration system.

Every assigned architecture is expressed as a :class:`ModelConfig`.  A config
fully determines parameter shapes, the layer pattern (including hybrid
attention/SSM interleaves and local:global attention schedules), the MoE and
MLA sub-specs, and the modality frontend stubs.

Configs are *frozen* dataclasses so they can be used as static args to
``jax.jit`` and hashed into compilation caches.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional, Tuple

# --------------------------------------------------------------------------
# Sub-specs
# --------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class MoESpec:
    """Mixture-of-experts FFN spec (GShard-style top-k with capacity)."""

    n_experts: int
    top_k: int
    d_ff_expert: int
    capacity_factor: float = 1.25
    # qwen3-style: softmax over the selected top-k logits (renormalized);
    # if False: softmax over all experts then select (switch-style).
    norm_topk_prob: bool = True
    router_dtype: str = "float32"
    # Expert parallelism's share: this chip holds the first n_local_experts
    # of the router's n_experts (None: all of them).
    n_local_experts: Optional[int] = None
    # Shared experts (DeepSeek): one SwiGLU MLP of this width beside the
    # routed ones, applied to every token (0: none).
    d_ff_shared: int = 0

    @property
    def n_held(self) -> int:
        return self.n_experts if self.n_local_experts is None \
            else self.n_local_experts

    @property
    def holds_share(self) -> bool:
        """True where the capacity path cannot run this layer: it holds a
        share of the experts, or has shared experts."""
        return self.n_held != self.n_experts or self.d_ff_shared > 0


@dataclasses.dataclass(frozen=True)
class SSMSpec:
    """Mamba-2 SSD spec."""

    d_state: int = 128
    d_conv: int = 4
    expand: int = 2
    head_dim: int = 64
    n_groups: int = 1
    chunk: int = 256

    def d_inner(self, d_model: int) -> int:
        return self.expand * d_model

    def n_heads(self, d_model: int) -> int:
        return self.d_inner(d_model) // self.head_dim


@dataclasses.dataclass(frozen=True)
class MLASpec:
    """Multi-head latent attention (DeepSeek-V2 / MiniCPM3).  A
    ``q_lora_rank`` of None projects queries directly (DeepSeek-V2-Lite)."""

    q_lora_rank: Optional[int] = 768
    kv_lora_rank: int = 256
    qk_nope_head_dim: int = 64
    qk_rope_head_dim: int = 32
    v_head_dim: int = 64

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim


@dataclasses.dataclass(frozen=True)
class YaRNSpec:
    """YaRN rope scaling (arXiv:2309.00071), as DeepSeek-V2's config states
    it under ``rope_scaling``."""

    factor: float
    original_max_position_embeddings: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0


@dataclasses.dataclass(frozen=True)
class EncoderSpec:
    """Encoder stack for enc-dec models (whisper).  Bidirectional attention."""

    n_layers: int = 6
    n_heads: int = 8
    n_kv_heads: int = 8
    d_ff: int = 2048
    # Number of (precomputed, stubbed) frontend frames fed to the encoder.
    source_len: int = 1500


# --------------------------------------------------------------------------
# Main config
# --------------------------------------------------------------------------

MIXERS = ("attn", "attn_local", "ssm")
MLPS = ("mlp", "moe")


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str  # dense | ssm | moe | hybrid | vlm | audio
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_head: int
    d_ff: int
    vocab_size: int

    # Layer pattern: one *period* of mixer kinds / mlp kinds; the model is
    # ``n_layers // len(pattern)`` scanned periods plus an unrolled remainder
    # of ``pattern[: n_layers % len(pattern)]``.
    layer_pattern: Tuple[str, ...] = ("attn",)
    mlp_pattern: Tuple[str, ...] = ("mlp",)
    # Leading layers of ``layer_pattern[0]``'s mixer with a dense MLP of
    # ``d_ff`` (DeepSeek's first_k_dense_replace), unrolled before the
    # scanned stack; they count in ``n_layers``.
    n_dense_layers: int = 0

    qkv_bias: bool = False
    rope_theta: float = 10_000.0
    # gemma3: local (sliding-window) layers use a different rope base.
    rope_theta_local: Optional[float] = None
    rope_scaling: Optional[YaRNSpec] = None
    embed_scale: float = 1.0
    norm_eps: float = 1e-5
    tie_embeddings: bool = False
    attn_window: Optional[int] = None  # window for 'attn_local' layers
    attn_logit_softcap: Optional[float] = None
    act: str = "swiglu"  # swiglu | gelu

    moe: Optional[MoESpec] = None
    ssm: Optional[SSMSpec] = None
    mla: Optional[MLASpec] = None
    encoder: Optional[EncoderSpec] = None

    # Modality frontend stub: 'none' | 'patch_stub' (vlm) | 'audio_stub'.
    frontend: str = "none"
    frontend_len: int = 0  # precomputed embeddings prepended to the sequence

    dtype: str = "bfloat16"
    # Cross-entropy is computed in sequence chunks of this size so the full
    # [B, S, V] logits tensor is never materialized (vocab up to 262k).
    loss_chunk: int = 512
    # Query-chunk size for the HLO-level flash attention scan.
    attn_chunk: int = 1024
    # Remat ("activation checkpoint") policy for scanned blocks:
    # 'none' | 'full' | 'dots'.
    remat: str = "full"
    # Optimizer moment dtype ('float32' normally; 'bfloat16' for 398B jamba
    # so optimizer state fits pod HBM).
    opt_dtype: str = "float32"

    # ---------------------------------------------------------------- helpers
    def __post_init__(self):
        assert self.family in ("dense", "ssm", "moe", "hybrid", "vlm", "audio")
        assert len(self.layer_pattern) == len(self.mlp_pattern)
        for m in self.layer_pattern:
            assert m in MIXERS, m
        for m in self.mlp_pattern:
            assert m in MLPS, m
        if "ssm" in self.layer_pattern:
            assert self.ssm is not None
        if "moe" in self.mlp_pattern:
            assert self.moe is not None
        if "attn_local" in self.layer_pattern:
            assert self.attn_window is not None

    @property
    def period(self) -> int:
        return len(self.layer_pattern)

    @property
    def n_periods(self) -> int:
        return (self.n_layers - self.n_dense_layers) // self.period

    @property
    def n_remainder(self) -> int:
        return (self.n_layers - self.n_dense_layers) % self.period

    @property
    def is_encdec(self) -> bool:
        return self.encoder is not None

    @property
    def uses_attention(self) -> bool:
        return any(m.startswith("attn") for m in self.layer_pattern)

    @property
    def sub_quadratic(self) -> bool:
        """True if the arch can run long_500k (SSM/hybrid)."""
        return self.family in ("ssm", "hybrid")

    # -------------------------------------------------------------- counting
    def param_count(self) -> int:
        """Exact parameter count (matches init_params; used for 6ND)."""
        from repro.models.model import count_params_analytic

        return count_params_analytic(self)

    def active_param_count(self) -> int:
        """Active (per-token) parameters, for MoE 6·N_active·D."""
        from repro.models.model import count_params_analytic

        return count_params_analytic(self, active_only=True)


# --------------------------------------------------------------------------
# Registry
# --------------------------------------------------------------------------

_REGISTRY: dict = {}


def register(cfg: ModelConfig) -> ModelConfig:
    if cfg.name in _REGISTRY:
        raise ValueError(f"duplicate arch {cfg.name}")
    _REGISTRY[cfg.name] = cfg
    return cfg


def get_config(name: str) -> ModelConfig:
    _ensure_loaded()
    if name not in _REGISTRY:
        raise KeyError(f"unknown arch {name!r}; have {sorted(_REGISTRY)}")
    return _REGISTRY[name]


def list_archs() -> list:
    _ensure_loaded()
    return sorted(_REGISTRY)


def _ensure_loaded():
    # Import every per-arch config module exactly once.
    import repro.configs.archs  # noqa: F401


# --------------------------------------------------------------------------
# Reduced configs for CPU smoke tests
# --------------------------------------------------------------------------


def reduced_config(name_or_cfg) -> ModelConfig:
    """A tiny config of the *same family / layer pattern* for CPU smoke tests.

    Keeps the period structure (so hybrid/local-global/moe code paths are
    exercised) while shrinking widths, depth, vocab, experts.
    """
    cfg = name_or_cfg if isinstance(name_or_cfg, ModelConfig) else get_config(name_or_cfg)
    period = cfg.period
    n_dense = min(cfg.n_dense_layers, 1)
    n_layers = n_dense + period + min(cfg.n_remainder, 1)
    d_model = 64
    n_heads = 4
    n_kv = min(cfg.n_kv_heads, 2) if cfg.n_kv_heads < cfg.n_heads else 4
    moe = None
    if cfg.moe is not None:
        # capacity_factor = E/k guarantees C >= tokens-per-group, i.e. no
        # capacity drops — keeps smoke tests deterministic w.r.t. grouping.
        moe = dataclasses.replace(
            cfg.moe, n_experts=8, top_k=min(cfg.moe.top_k, 2), d_ff_expert=32,
            capacity_factor=4.0, n_local_experts=None,
            d_ff_shared=64 if cfg.moe.d_ff_shared else 0)
    ssm = None
    if cfg.ssm is not None:
        ssm = dataclasses.replace(cfg.ssm, d_state=16, head_dim=16, chunk=16)
    mla = None
    if cfg.mla is not None:
        mla = MLASpec(q_lora_rank=32 if cfg.mla.q_lora_rank else None,
                      kv_lora_rank=16, qk_nope_head_dim=8,
                      qk_rope_head_dim=8, v_head_dim=8)
    enc = None
    if cfg.encoder is not None:
        enc = EncoderSpec(n_layers=2, n_heads=4, n_kv_heads=4, d_ff=64,
                          source_len=16)
    return dataclasses.replace(
        cfg,
        name=cfg.name + "-reduced",
        n_layers=n_layers,
        n_dense_layers=n_dense,
        embed_scale=math.sqrt(d_model) if cfg.embed_scale != 1.0 else 1.0,
        d_model=d_model,
        n_heads=n_heads,
        n_kv_heads=n_kv,
        d_head=16,
        d_ff=128,
        vocab_size=512,
        moe=moe,
        ssm=ssm,
        mla=mla,
        encoder=enc,
        attn_window=min(cfg.attn_window, 8) if cfg.attn_window else None,
        frontend_len=8 if cfg.frontend != "none" else 0,
        loss_chunk=32,
        attn_chunk=16,
        dtype="float32",
        remat="none",
    )
