"""Model/shape configuration system.

Every architecture in the assigned pool is expressed as a ``ModelConfig``:
a declarative description from which the composable model builder
(``repro.core.model``) derives its layer plan, parameter shapes, sharding
plan and FLOP/byte counts.  Configs are registered by id and selectable via
``--arch <id>`` in every launcher.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Optional, Sequence

# ---------------------------------------------------------------------------
# Layer specs
# ---------------------------------------------------------------------------

# Attention kinds
ATTN_FULL = "full"          # global causal (or bidirectional for encoders)
ATTN_WINDOW = "window"      # sliding-window causal
ATTN_NONE = "none"          # attention-free (pure SSM layer)

# Mixer kinds
MIX_ATTN = "attn"           # plain MHSA/GQA
MIX_SSM = "ssm"             # mamba2 SSD block
MIX_HYBRID = "hybrid"       # parallel attn + ssm heads (hymba)
MIX_NONE = "none"           # no mixer (an FFN- or expert-only layer)

# FFN kinds
FFN_DENSE = "dense"         # (gated) MLP
FFN_MOE = "moe"             # mixture of experts
FFN_NONE = "none"           # no FFN (mamba2 blocks)


@dataclass(frozen=True)
class LayerSpec:
    """One transformer layer's structure."""
    mixer: str = MIX_ATTN                 # attn | ssm | hybrid | none
    attn: str = ATTN_FULL                 # full | window | none
    ffn: str = FFN_DENSE                  # dense | moe | none
    cross_attn: bool = False              # decoder cross-attention (enc-dec)
    d_ff: int = 0                         # dense FFN width for THIS layer

    def cache_kinds(self):
        kinds = []
        if self.mixer in (MIX_ATTN, MIX_HYBRID) and self.attn != ATTN_NONE:
            kinds.append("kv")
        if self.mixer in (MIX_SSM, MIX_HYBRID):
            kinds.append("ssm")
        if self.cross_attn:
            kinds.append("cross_kv")
        return kinds


@dataclass(frozen=True)
class LayerGroup:
    """``n_reps`` repetitions of a (short) layer pattern, run under lax.scan.

    Stacked parameters for the group have leading axis ``n_reps``; the HLO
    contains the pattern body once => bounded compile time for deep models.
    """
    n_reps: int
    pattern: tuple  # tuple[LayerSpec, ...]

    @property
    def n_layers(self) -> int:
        return self.n_reps * len(self.pattern)


# ---------------------------------------------------------------------------
# Model config
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                       # dense | moe | ssm | hybrid | encdec | vlm | encoder
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 0                 # 0 -> d_model // n_heads

    # --- attention features -------------------------------------------------
    qk_norm: bool = False
    rope_theta: float = 10_000.0
    sliding_window: int = 0           # 0 -> no SWA anywhere
    local_global_ratio: int = 0       # k -> k local layers per 1 global (gemma3)
    causal: bool = True               # False for encoders
    attn_scale: Optional[float] = None

    # --- layer pattern ------------------------------------------------------
    # One character per layer, each layer one pre-norm residual sublayer:
    # "M" an SSM mixer, "E" the expert FFN, "*" attention.  "" -> every
    # layer's kind follows ``family``.
    layer_pattern: str = ""

    # --- FFN / MoE ----------------------------------------------------------
    act: str = "silu"                 # silu (gated) | gelu | relu2
    gated_ffn: bool = True
    n_experts: int = 0
    n_shared_experts: int = 0
    top_k: int = 0
    moe_d_ff: int = 0                 # per-expert intermediate size
    first_k_dense: int = 0            # deepseek: first k layers use dense FFN
    dense_ff_override: int = 0        # width of those dense layers
    shared_d_ff: int = 0              # shared-expert width (0 -> moe_d_ff
                                      #   * n_shared_experts)
    router: str = "softmax"           # softmax | sigmoid: scores by sigmoid,
                                      #   top-k on score + a correction bias,
                                      #   weights renormalised and scaled by
                                      #   routed_scale
    routed_scale: float = 1.0
    expert_first: int = 0             # the experts this layer holds:
    experts_held: int = 0             #   [first, first + held); 0 -> all

    # --- SSM (mamba2 / SSD) ---------------------------------------------------
    ssm_state: int = 0
    ssm_expand: int = 2
    ssm_head_dim: int = 64
    ssm_conv: int = 4
    ssm_chunk: int = 256              # SSD chunk length
    ssm_heads: int = 0                # 0 -> ssm_expand * d_model // ssm_head_dim
    ssm_groups: int = 1               # B/C groups (heads share a group's B/C)
    ssm_conv_bias: bool = False

    # --- enc-dec / frontends --------------------------------------------------
    n_enc_layers: int = 0             # >0 -> encoder-decoder
    enc_seq_len: int = 0              # fixed encoder memory length for decode shapes
    frontend: Optional[str] = None    # audio_frames | vision_patches (stub per spec)
    n_frontend_embeds: int = 0        # patches/frames provided as precomputed embeds

    # --- misc -----------------------------------------------------------------
    sandwich_norm: bool = False       # gemma3: post-sublayer norms
    scale_embed: bool = False         # gemma3: embeddings scaled by sqrt(E)
    norm: str = "rmsnorm"             # rmsnorm | layernorm
    norm_eps: float = 1e-6
    tie_embeddings: bool = True
    dtype: str = "bfloat16"
    max_seq_len: int = 131_072
    source: str = ""                  # provenance note

    # ------------------------------------------------------------------ utils
    @property
    def head_dim_(self) -> int:
        return self.head_dim or self.d_model // self.n_heads

    @property
    def ssm_heads_(self) -> int:
        return self.ssm_heads or \
            self.ssm_expand * self.d_model // self.ssm_head_dim

    @property
    def ssm_inner(self) -> int:
        return self.ssm_heads_ * self.ssm_head_dim

    @property
    def experts_held_(self) -> int:
        return self.experts_held or self.n_experts

    @property
    def shared_d_ff_(self) -> int:
        return self.shared_d_ff or self.moe_d_ff * self.n_shared_experts

    @property
    def is_encdec(self) -> bool:
        return self.n_enc_layers > 0

    @property
    def is_encoder_only(self) -> bool:
        return self.family == "encoder"

    @property
    def has_decode(self) -> bool:
        return not self.is_encoder_only

    # ---------------------------------------------------------------- layers
    def layer_specs(self) -> list:
        """Per-layer structure for the decoder (or encoder-only) stack."""
        specs = []
        for i in range(self.n_layers):
            specs.append(self._spec_for_layer(i))
        return specs

    def _spec_for_layer(self, i: int) -> LayerSpec:
        if self.layer_pattern:
            return PATTERN_SPECS[self.layer_pattern[i]]
        # attention kind
        if self.family == "ssm":
            return LayerSpec(mixer=MIX_SSM, attn=ATTN_NONE, ffn=FFN_NONE)
        if self.local_global_ratio > 0:
            k = self.local_global_ratio
            attn = ATTN_FULL if (i % (k + 1)) == k else ATTN_WINDOW
        elif self.sliding_window > 0:
            attn = ATTN_WINDOW
        else:
            attn = ATTN_FULL
        mixer = MIX_HYBRID if self.family == "hybrid" else MIX_ATTN
        if self.family == "hybrid":
            # hymba: a few strategically-placed full-attention layers
            full_at = {0, self.n_layers // 2, self.n_layers - 1}
            attn = ATTN_FULL if i in full_at else ATTN_WINDOW
        # ffn kind
        if self.family == "ssm":
            ffn, d_ff = FFN_NONE, 0
        elif self.n_experts > 0 and i >= self.first_k_dense:
            ffn, d_ff = FFN_MOE, 0
        elif self.n_experts > 0:
            ffn, d_ff = FFN_DENSE, (self.dense_ff_override or self.d_ff)
        else:
            ffn, d_ff = FFN_DENSE, self.d_ff
        return LayerSpec(mixer=mixer, attn=attn, ffn=ffn, d_ff=d_ff,
                         cross_attn=self.is_encdec)

    def encoder_layer_specs(self) -> list:
        return [LayerSpec(mixer=MIX_ATTN, attn=ATTN_FULL, ffn=FFN_DENSE,
                          d_ff=self.d_ff, cross_attn=False)
                for _ in range(self.n_enc_layers)]

    def layer_groups(self, specs: Optional[Sequence[LayerSpec]] = None) -> list:
        """Factor the layer list into scanned (n_reps x pattern) groups."""
        specs = list(specs if specs is not None else self.layer_specs())
        return factor_layer_groups(specs)

    # ------------------------------------------------------------- accounting
    def param_count(self) -> int:
        """Exact parameter count (matches init_params)."""
        from repro.core import model as _model  # lazy; avoids jax import here
        return _model.param_count(self)

    def window_for(self, spec: LayerSpec) -> int:
        return self.sliding_window if spec.attn == ATTN_WINDOW else 0


# The layer kinds of ``ModelConfig.layer_pattern``.
PATTERN_SPECS = {
    "M": LayerSpec(mixer=MIX_SSM, attn=ATTN_NONE, ffn=FFN_NONE),
    "E": LayerSpec(mixer=MIX_NONE, attn=ATTN_NONE, ffn=FFN_MOE),
    "*": LayerSpec(mixer=MIX_ATTN, attn=ATTN_FULL, ffn=FFN_NONE),
}


def factor_layer_groups(specs) -> list:
    """Greedy periodic factoring: find the shortest repeating pattern prefix,
    emit (reps, pattern) groups; remainder becomes its own group(s)."""
    groups = []
    i = 0
    n = len(specs)
    while i < n:
        # find longest run of a minimal period starting at i
        best = (1, 1)  # (period, reps)
        for period in (1, 2, 3, 4, 6, 7, 8):
            if i + period > n:
                break
            reps = 1
            while i + (reps + 1) * period <= n and \
                    specs[i + reps * period: i + (reps + 1) * period] == specs[i: i + period]:
                reps += 1
            if reps * period > best[0] * best[1] or \
                    (reps * period == best[0] * best[1] and period < best[0]):
                best = (period, reps)
        period, reps = best
        groups.append(LayerGroup(n_reps=reps, pattern=tuple(specs[i:i + period])))
        i += period * reps
    return groups


# ---------------------------------------------------------------------------
# Input shapes ("cells")
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class ShapeConfig:
    name: str
    kind: str           # train | prefill | decode
    seq_len: int
    global_batch: int


SHAPES = {
    "train_4k":    ShapeConfig("train_4k", "train", 4_096, 256),
    "prefill_32k": ShapeConfig("prefill_32k", "prefill", 32_768, 32),
    "decode_32k":  ShapeConfig("decode_32k", "decode", 32_768, 128),
    "long_500k":   ShapeConfig("long_500k", "decode", 524_288, 1),
}

# archs with sub-quadratic structure (SSM / SWA-dominant) run long_500k
_SUBQUADRATIC = {"mamba2-370m", "hymba-1.5b", "gemma3-12b", "gemma3-27b",
                 "mixtral-8x22b"}


def shape_supported(cfg: ModelConfig, shape: ShapeConfig):
    """-> (supported, reason_if_not)."""
    if shape.kind == "decode" and not cfg.has_decode:
        return False, "encoder-only: no decode step"
    if shape.name == "long_500k" and cfg.name not in _SUBQUADRATIC:
        return False, "pure full-attention arch: long_500k skipped (DESIGN.md)"
    return True, ""


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------

_REGISTRY: dict = {}


def register(cfg: ModelConfig) -> ModelConfig:
    assert cfg.name not in _REGISTRY, f"duplicate config {cfg.name}"
    _REGISTRY[cfg.name] = cfg
    return cfg


def get_config(name: str) -> ModelConfig:
    _ensure_loaded()
    if name not in _REGISTRY:
        raise KeyError(f"unknown arch '{name}'; have {sorted(_REGISTRY)}")
    return _REGISTRY[name]


def list_configs() -> list:
    _ensure_loaded()
    return sorted(_REGISTRY)


ASSIGNED = [
    "mamba2-370m", "gemma3-12b", "gemma3-27b", "qwen3-0.6b",
    "mistral-large-123b", "deepseek-moe-16b", "mixtral-8x22b",
    "seamless-m4t-large-v2", "hymba-1.5b", "pixtral-12b",
    "nemotron3-nano-30b-a3b",
]

PAPER_MODELS = ["tinyllama-42m", "tinyllama-42m-64h", "mobilebert"]


def _ensure_loaded():
    # import every config module exactly once
    if _REGISTRY:
        return
    from repro.configs import (  # noqa: F401
        mamba2_370m, gemma3, qwen3, mistral_large, deepseek_moe, mixtral,
        seamless_m4t, hymba, pixtral, paper_models, nemotron_h,
    )


def reduced(cfg: ModelConfig, **overrides) -> ModelConfig:
    """A tiny same-family config for CPU smoke tests."""
    scale = dict(
        n_layers=min(cfg.n_layers, 2 + (2 if cfg.local_global_ratio else 0)),
        d_model=128,
        n_heads=4,
        n_kv_heads=min(cfg.n_kv_heads, 2) if cfg.n_kv_heads < cfg.n_heads else 4,
        head_dim=32,
        d_ff=256 if cfg.d_ff else 0,
        vocab_size=512,
        max_seq_len=512,
    )
    if cfg.local_global_ratio:
        scale["n_layers"] = cfg.local_global_ratio + 1  # one full pattern
        scale["sliding_window"] = 64
    elif cfg.sliding_window:
        scale["sliding_window"] = 64
    if cfg.n_experts:
        n_exp = min(cfg.n_experts, 8)
        scale.update(n_experts=n_exp,
                     top_k=min(cfg.top_k, 2),
                     n_shared_experts=min(cfg.n_shared_experts, 1),
                     moe_d_ff=64, first_k_dense=min(cfg.first_k_dense, 1),
                     dense_ff_override=256 if cfg.first_k_dense else 0,
                     shared_d_ff=128 if cfg.shared_d_ff else 0)
        if cfg.experts_held:        # the same share of fewer experts
            scale.update(
                expert_first=cfg.expert_first * n_exp // cfg.n_experts,
                experts_held=max(2, cfg.experts_held * n_exp
                                 // cfg.n_experts))
    if cfg.ssm_state:
        scale.update(ssm_state=16, ssm_head_dim=16, ssm_chunk=32)
        if cfg.ssm_heads:
            scale.update(ssm_heads=8, ssm_groups=min(cfg.ssm_groups, 2))
    if cfg.is_encdec:
        scale.update(n_enc_layers=2, enc_seq_len=64)
    if cfg.n_frontend_embeds:
        scale.update(n_frontend_embeds=16)
    if cfg.family == "hybrid":
        scale.update(n_layers=4)
    if cfg.layer_pattern:           # one whole period of the pattern
        period = len(cfg.layer_groups()[0].pattern)
        scale.update(n_layers=period,
                     layer_pattern=cfg.layer_pattern[:period])
    scale.update(overrides)
    return dataclasses.replace(cfg, name=cfg.name + "-smoke", **scale)
