"""The paper's partitioning scheme as a first-class object: ``ShardingPlan``.

Maps §IV of the paper onto a TPU mesh:

* head-parallel split of W_Q/W_K/W_V (and SSD heads) on the ``model`` axis,
* W_O split along its input (head*P) dimension,
* FFN weights sliced along the intermediate F dimension (per-expert for MoE),
* embedding / LM head sliced along vocab,
* **zero weight duplication** across the TP group (audited; documented
  exceptions: GQA KV-head replication when tp > n_kv, SSD B/C/dt
  projections with n_groups=1, and zero-padding for indivisible head
  counts — all quantified by ``duplication_report``),
* exactly **two synchronizations per block** (one post-attention, one
  post-FFN), enforced via explicit ledger-instrumented psums.

Every TP-sharded parameter carries an explicit leading ``tp`` shard axis,
sharded ``P(plan.tp_axis)``; inside ``shard_map`` each device sees its
``(1, ...)`` slice.  This makes "which chip holds what" a static, auditable
property — the on-chip-stationary invariant of the paper.
"""
from __future__ import annotations

from dataclasses import dataclass, replace
from typing import Optional


from repro.configs.base import ModelConfig


def ceil_to(x: int, m: int) -> int:
    return -(-x // m) * m


# ---------------------------------------------------------------------------
# Plan
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class ShardingPlan:
    """How the model is laid out on the mesh (paper-faithful by default)."""
    tp: int = 1                       # model-axis size (the paper's Num_Chips)
    tp_axis: str = "model"
    dp_axes: tuple = ("data",)        # batch axes (("pod","data") multi-pod)
    seq_shard_kv: bool = False        # long-context decode: shard KV seq on dp
    activations: str = "replicated"   # replicated (paper) | seq (RS+AG, beyond-paper)
    moe_mode: str = "tp"              # tp (paper-faithful F-slice) | ep (all_to_all)
    remat: str = "none"               # none | block (training)
    kv_cache_dtype: str = "bfloat16"  # "int8": quantized page pools with
                                      #   per-(page, slot) scales (paged) /
                                      #   fixed-point lanes (contiguous)
    kv_quant_scale: float = 16.0      # fixed-point scale for int8 KV
    ssm_cache_dtype: str = ""         # "" -> float32 slabs; "int8": quantized
                                      #   state slabs with per-slab-head scales
    weight_dtype: str = ""            # "" -> cfg.dtype; "int8" for deployment
    attn_scheme: str = "scan"         # scan (baseline) | split (4/3 causal)
    cp_axes: tuple = ()               # context parallelism: shard S over these
    cp_state_dtype: str = "float32"   # SSD state-gather precision (bf16: half wire)
    dp_hierarchical: bool = True      # grads: RS in-pod + AR cross-pod + AG
    zero1: bool = False               # shard optimizer state over the data axis

    @property
    def all_data_axes(self) -> tuple:
        return self.dp_axes

    @property
    def tp_axes(self) -> tuple:
        """Axes carrying the paper's tensor parallelism (empty when tp=1,
        e.g. under pure context parallelism)."""
        return (self.tp_axis,) if self.tp > 1 else ()

    @property
    def grad_axes(self) -> tuple:
        return tuple(self.dp_axes) + tuple(self.cp_axes)

    def axis_sizes(self, mesh) -> dict:
        return {name: size for name, size in
                zip(mesh.axis_names, mesh.devices.shape, strict=True)}

    def with_(self, **kw) -> "ShardingPlan":
        return replace(self, **kw)


# ---------------------------------------------------------------------------
# Head layout (handles GQA replication + indivisible head padding)
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class HeadLayout:
    n_q: int                 # real q heads
    n_kv: int                # real kv heads
    tp: int
    hq_pad: int              # padded q heads (multiple of tp)
    hq_loc: int              # q heads per shard
    r: int                   # q heads per local kv slot (uniform)
    n_kv_loc: int            # kv slots per shard
    kv_map: tuple            # (tp, n_kv_loc) global kv head per slot
    q_valid: tuple           # (tp, hq_loc) 1.0 for real q heads

    @property
    def kv_slots_total(self) -> int:
        return self.tp * self.n_kv_loc

    @property
    def kv_duplication(self) -> float:
        """Stored kv-head slots / real kv heads (1.0 = zero duplication)."""
        return self.kv_slots_total / self.n_kv


def head_layout(n_q: int, n_kv: int, tp: int) -> HeadLayout:
    assert n_q % n_kv == 0, (n_q, n_kv)
    group = n_q // n_kv
    hq_pad = ceil_to(n_q, tp)
    hq_loc = hq_pad // tp

    def kv_of(h):  # padded q heads borrow the last real kv head (weights are 0)
        return min(h, n_q - 1) // group

    # largest r dividing hq_loc s.t. each slot's r consecutive q heads share a kv
    r = hq_loc
    while r > 1:
        ok = all(
            len({kv_of(i * hq_loc + s * r + j) for j in range(r)}) == 1
            for i in range(tp) for s in range(hq_loc // r)
        )
        if ok:
            break
        r //= 2
    n_kv_loc = hq_loc // r
    kv_map = tuple(tuple(kv_of(i * hq_loc + s * r) for s in range(n_kv_loc))
                   for i in range(tp))
    q_valid = tuple(tuple(1.0 if i * hq_loc + j < n_q else 0.0
                          for j in range(hq_loc)) for i in range(tp))
    return HeadLayout(n_q, n_kv, tp, hq_pad, hq_loc, r, n_kv_loc, kv_map, q_valid)


@dataclass(frozen=True)
class DimLayout:
    """A plain dimension sliced across tp with zero-padding (FFN F, vocab V)."""
    n: int
    tp: int
    n_pad: int
    loc: int

    @property
    def pad_waste(self) -> float:
        return (self.n_pad - self.n) / self.n


def dim_layout(n: int, tp: int) -> DimLayout:
    n_pad = ceil_to(n, tp)
    return DimLayout(n, tp, n_pad, n_pad // tp)


# ---------------------------------------------------------------------------
# Whole-model layout
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class ModelLayout:
    attn: HeadLayout
    ssm: Optional[HeadLayout]
    ffn: DimLayout                  # dense FFN F
    moe_ffn: Optional[DimLayout]    # per-expert F (tp mode)
    shared_ffn: Optional[DimLayout]
    dense_override_ffn: Optional[DimLayout]
    vocab: DimLayout
    experts: Optional[DimLayout]    # expert count split (ep mode)


def model_layout(cfg: ModelConfig, plan: ShardingPlan) -> ModelLayout:
    tp = plan.tp
    attn = head_layout(cfg.n_heads, cfg.n_kv_heads, tp)
    ssm = None
    if cfg.ssm_state:
        ssm = head_layout(cfg.ssm_heads_, cfg.ssm_heads_, tp)
    moe_ffn = dim_layout(cfg.moe_d_ff, tp) if cfg.n_experts else None
    shared = (dim_layout(cfg.shared_d_ff_, tp)
              if cfg.n_shared_experts else None)
    dense_override = (dim_layout(cfg.dense_ff_override, tp)
                      if cfg.dense_ff_override else None)
    experts = dim_layout(cfg.experts_held_, tp) if (
        cfg.n_experts and plan.moe_mode == "ep") else None
    return ModelLayout(
        attn=attn,
        ssm=ssm,
        ffn=dim_layout(cfg.d_ff, tp) if cfg.d_ff else dim_layout(0, 1),
        moe_ffn=moe_ffn,
        shared_ffn=shared,
        dense_override_ffn=dense_override,
        vocab=dim_layout(cfg.vocab_size, tp),
        experts=experts,
    )


# ---------------------------------------------------------------------------
# Zero-duplication audit (paper Table I property, enforced in tests)
# ---------------------------------------------------------------------------

def duplication_report(cfg: ModelConfig, plan: ShardingPlan) -> dict:
    """Bytes stored beyond one copy of the real weights, per category."""
    lay = model_layout(cfg, plan)
    d = cfg.head_dim_
    E = cfg.d_model
    per_layer_pad = 0.0
    specs = cfg.layer_specs()
    n_attn = sum(1 for s in specs if s.mixer in ("attn", "hybrid"))
    n_ssm = sum(1 for s in specs if s.mixer in ("ssm", "hybrid"))
    # KV replication + q padding (attention)
    hl = lay.attn
    kv_extra_heads = hl.kv_slots_total - hl.n_kv
    q_extra_heads = hl.hq_pad - hl.n_q
    attn_dup = n_attn * kv_extra_heads * E * d * 2 * 2      # wk+wv, bf16
    attn_pad = n_attn * q_extra_heads * E * d * 2 * 2       # wq+wo
    # SSD B/C/dt replicated (n_groups=1)
    ssm_dup = 0.0
    if lay.ssm is not None:
        N = cfg.ssm_state
        ssm_dup = n_ssm * (plan.tp - 1) * (2 * E * N + 2 * N * cfg.ssm_conv) * 2
        ssm_pad = n_ssm * (lay.ssm.hq_pad - lay.ssm.n_q) * (
            2 * E * cfg.ssm_head_dim + cfg.ssm_head_dim * E) * 2
        per_layer_pad += ssm_pad
    # FFN/vocab padding
    ffn_pad = sum((dim_layout(s.d_ff, plan.tp).n_pad - s.d_ff) * 3 * E * 2
                  for s in specs if s.ffn == "dense" and s.d_ff)
    vocab_pad = (lay.vocab.n_pad - lay.vocab.n) * E * 2 * (1 if cfg.tie_embeddings else 2)
    from repro.core import model as _m
    total = _m.param_count(cfg) * 2  # bf16 bytes, single copy
    dup = attn_dup + ssm_dup
    pad = attn_pad + per_layer_pad + ffn_pad + vocab_pad
    return {
        "single_copy_bytes": total,
        "duplicated_bytes": dup,
        "padded_bytes": pad,
        "dup_fraction": dup / total,
        "pad_fraction": pad / total,
        "zero_dup_core": dup == 0.0,
    }
