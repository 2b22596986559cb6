"""Analytic FLOP/byte model — the roofline's compute & memory terms.

``cost_analysis()`` on a scanned module reports ONE iteration of each
``while`` loop (verified experimentally), so scanned layer stacks would be
undercounted ~n_layers x.  This module therefore derives per-device FLOPs
and HBM bytes *analytically* from (config x shape x plan) — exact for the
ops we emit, including padding waste, the scan-flash causal 2x overhead,
MoE capacity factors and KV traffic.  ``tests/test_analytics.py`` validates
it against ``cost_analysis`` on small UNROLLED modules.

All numbers are PER DEVICE per step.
"""
from __future__ import annotations

from dataclasses import dataclass, field

import numpy as np

from repro.configs.base import (ATTN_WINDOW, FFN_DENSE, FFN_MOE, MIX_ATTN,
                                MIX_HYBRID, MIX_SSM, ModelConfig,
                                ShapeConfig)
from repro.core.partition import ShardingPlan, dim_layout, model_layout

BF16 = 2
F32 = 4

# scan-based flash attention computes all kv chunks for full-causal layers
CAUSAL_SCAN_WASTE = 2.0


@dataclass
class Cost:
    flops: dict = field(default_factory=dict)    # category -> flops/device
    bytes_hbm: dict = field(default_factory=dict)

    def add_flops(self, cat, n):
        self.flops[cat] = self.flops.get(cat, 0.0) + float(n)

    def add_bytes(self, cat, n):
        self.bytes_hbm[cat] = self.bytes_hbm.get(cat, 0.0) + float(n)

    @property
    def total_flops(self):
        return sum(self.flops.values())

    @property
    def total_bytes(self):
        return sum(self.bytes_hbm.values())

    def merged(self, other, scale=1.0):
        out = Cost(dict(self.flops), dict(self.bytes_hbm))
        for k, v in other.flops.items():
            out.flops[k] = out.flops.get(k, 0.0) + v * scale
        for k, v in other.bytes_hbm.items():
            out.bytes_hbm[k] = out.bytes_hbm.get(k, 0.0) + v * scale
        return out


def _mm(cost, cat, m, k, n, w_dtype=BF16, count=1.0):
    """One matmul (m,k)@(k,n): flops + operand/result HBM traffic.
    The (k,n) operand is the WEIGHT (read at w_dtype); activations at bf16.
    Weight traffic is therefore counted exactly once per use — there is no
    separate blanket weights category."""
    cost.add_flops(cat, 2.0 * m * k * n * count)
    cost.add_bytes(cat, ((m * k + m * n) * BF16 + k * n * w_dtype) * count)


def layer_cost(cfg: ModelConfig, plan: ShardingPlan, spec, B: int, S: int,
               mode: str, kv_len: int) -> Cost:
    """One layer, per device.  B = local batch, S = tokens this step,
    kv_len = attention span (cache length for decode)."""
    lay = model_layout(cfg, plan)
    c = Cost()
    E = cfg.d_model
    d = cfg.head_dim_
    T = B * S
    wdt = 1 if plan.weight_dtype == "int8" else BF16

    # ---- attention ----------------------------------------------------------
    if spec.mixer in (MIX_ATTN, MIX_HYBRID):
        hl = lay.attn
        hq, nkv = hl.hq_loc, hl.n_kv_loc
        _mm(c, "qkvo", T, E, hq * d, w_dtype=wdt)                       # wq
        _mm(c, "qkvo", T, E, nkv * d, w_dtype=wdt, count=2.0)           # wk, wv
        _mm(c, "qkvo", T, hq * d, E, w_dtype=wdt)                       # wo
        if mode == "decode":
            span = min(kv_len, cfg.sliding_window) if \
                spec.attn == ATTN_WINDOW and cfg.sliding_window else kv_len
            c.add_flops("attn", 2.0 * B * hq * span * d * 2)
            kv_bytes = np.dtype(plan.kv_cache_dtype).itemsize
            ndp = 1
            if plan.seq_shard_kv:
                ndp = _ndp(plan)
            c.add_bytes("kv_cache", 2.0 * B * nkv * (span / ndp) * d * kv_bytes)
        else:
            if spec.attn == ATTN_WINDOW and cfg.sliding_window and \
                    S > cfg.sliding_window:
                span = cfg.sliding_window + 512            # + q-block slack
                c.add_flops("attn", 2.0 * B * hq * S * span * d * 2)
            else:
                waste = CAUSAL_SCAN_WASTE if (cfg.causal and S > 1024) else 1.0
                if plan.attn_scheme == "split" and cfg.causal and S > 1024:
                    waste = 4.0 / 3.0
                c.add_flops("attn", 2.0 * B * hq * S * kv_len * d * waste)
            c.add_bytes("attn_io", T * (hq + 2 * nkv) * d * BF16 * 2)
            if mode == "prefill":
                c.add_bytes("kv_cache", 2.0 * T * nkv * d *
                            np.dtype(plan.kv_cache_dtype).itemsize)

    # ---- cross attention ----------------------------------------------------
    if spec.cross_attn:
        hl = lay.attn
        hq, nkv = hl.hq_loc, hl.n_kv_loc
        _mm(c, "qkvo", T, E, hq * d, w_dtype=wdt)
        _mm(c, "qkvo", T, hq * d, E, w_dtype=wdt)
        Senc = cfg.enc_seq_len if mode == "decode" else kv_len
        if mode != "decode":
            _mm(c, "qkvo", B * Senc, E, nkv * d, w_dtype=wdt, count=2.0)
        c.add_flops("attn", 2.0 * B * hq * S * Senc * d * 2)

    # ---- SSD ---------------------------------------------------------------
    if spec.mixer in (MIX_SSM, MIX_HYBRID):
        sl = lay.ssm
        H = sl.hq_loc
        P = cfg.ssm_head_dim
        N = cfg.ssm_state
        _mm(c, "ssm_proj", T, E, 2 * H * P, w_dtype=wdt)                # in_z, in_x
        _mm(c, "ssm_proj", T, E, 2 * N + H, w_dtype=wdt)                # B, C, dt (replicated)
        _mm(c, "ssm_proj", T, H * P, E, w_dtype=wdt)                    # out
        if mode == "decode":
            c.add_flops("ssd", B * H * P * N * 4.0)
            c.add_bytes("ssd_state", B * H * P * N * F32 * 2)
        else:
            Q = cfg.ssm_chunk
            nc_ = -(-S // Q)
            # intra: G (Q^2 N) + W*xdt (Q^2 H P) ; inter: Q N H P
            c.add_flops("ssd", B * nc_ * (2.0 * Q * Q * N +
                                          2.0 * Q * Q * H * P +
                                          4.0 * Q * N * H * P))

    # ---- FFN ----------------------------------------------------------------
    nmat = 3 if cfg.gated_ffn else 2
    if spec.ffn == FFN_DENSE:
        f_loc = dim_layout(spec.d_ff, plan.tp).loc
        _mm(c, "ffn", T, E, f_loc, w_dtype=wdt, count=nmat - 1)
        _mm(c, "ffn", T, f_loc, E, w_dtype=wdt)
    elif spec.ffn == FFN_MOE:
        # dropless: every computed expert runs every token's row
        # (core/moe.py), the rows of tokens that did not pick it weighted 0
        if plan.moe_mode == "ep":
            n_loc = cfg.experts_held_ // plan.tp
            ftot = cfg.moe_d_ff
            _mm(c, "moe", n_loc * T, E, ftot, w_dtype=wdt, count=nmat - 1)
            _mm(c, "moe", n_loc * T, ftot, E, w_dtype=wdt)
        else:
            ef = lay.moe_ffn.loc
            n_held = cfg.experts_held_
            _mm(c, "moe", n_held * T, E, ef, w_dtype=wdt, count=nmat - 1)
            _mm(c, "moe", n_held * T, ef, E, w_dtype=wdt)
        c.add_flops("moe_router", 2.0 * T * E * cfg.n_experts)
        if cfg.n_shared_experts:
            sf = lay.shared_ffn.loc
            _mm(c, "ffn", T, E, sf, w_dtype=wdt, count=nmat - 1)
            _mm(c, "ffn", T, sf, E, w_dtype=wdt)

    # ---- norms / residuals (bandwidth only) ---------------------------------
    c.add_bytes("elementwise", 8.0 * T * E * BF16)
    c.add_flops("elementwise", 10.0 * T * E)
    return c


def _ndp(plan):
    # data-parallel degree is resolved by the caller via mesh sizes; the
    # plan-level fallback assumes the production 16-way data axis.
    return 16 * (2 if len(plan.dp_axes) > 1 else 1)


def step_cost(cfg: ModelConfig, plan: ShardingPlan, shape: ShapeConfig,
              mesh_sizes: dict) -> Cost:
    """Full per-device cost of one step of this cell."""
    ndp = int(np.prod([mesh_sizes.get(a, 1) for a in plan.dp_axes]))
    ncp = int(np.prod([mesh_sizes.get(a, 1) for a in plan.cp_axes]))
    B_glob, S_cell = shape.global_batch, shape.seq_len
    if plan.seq_shard_kv:
        B = B_glob
    else:
        B = max(1, B_glob // ndp)
    mode = {"train": "train", "prefill": "prefill", "decode": "decode"}[shape.kind]
    S = 1 if mode == "decode" else S_cell // ncp     # context-parallel slice
    kv_len = S_cell if mode == "decode" else S
    c = Cost()

    specs = cfg.layer_specs()
    for spec in specs:
        c = c.merged(layer_cost(cfg, plan, spec, B, S, mode, kv_len))
    if cfg.is_encdec and mode != "decode":
        for spec in cfg.encoder_layer_specs():
            c = c.merged(layer_cost(cfg, plan, spec, B, S_cell, "train",
                                    S_cell))

    # embed + lm head
    lay = model_layout(cfg, plan)
    T = B * S
    c.add_bytes("embed", T * cfg.d_model * BF16 +
                lay.vocab.loc * cfg.d_model * (1 if plan.weight_dtype ==
                                               "int8" else BF16))
    _mm(c, "lm_head", T, cfg.d_model, lay.vocab.loc,
        w_dtype=1 if plan.weight_dtype == "int8" else BF16)
    w_local = param_bytes_per_device(cfg, plan)

    if mode == "train":
        # backward ~2x forward flops (+1x recompute under block remat);
        # weights re-read + grads written + optimizer (m, v f32 read+write,
        # params read+write)
        mult = 2.0 + {"block": 1.0, "selective": 0.2}.get(plan.remat, 0.0)
        # backward also re-reads weights & activations: scale bytes too
        bwd = Cost({k: mult * v for k, v in c.flops.items()},
                   {k: mult * v for k, v in c.bytes_hbm.items()})
        c = c.merged(bwd)
        # grad write + optimizer traffic (m,v f32 read+write, params f32
        # read+write); ZeRO-1 divides the optimizer share by the data degree
        opt_share = (2 * 2 * 2 + 2)
        if plan.zero1:
            opt_share /= max(ndp, 1)
        c.add_bytes("grads_opt", w_local * (1 + opt_share))
        n_layers = cfg.n_layers + cfg.n_enc_layers
        tensors = {"none": 6.0, "selective": 3.0}.get(plan.remat, 1.0)
        c.add_bytes("activations", tensors * B * S * cfg.d_model * BF16 *
                    n_layers)
    return c


def param_bytes_per_device(cfg: ModelConfig, plan: ShardingPlan) -> float:
    """Per-device weight bytes = sharded layout total / tp (leading-axis
    sharded leaves) + replicated leaves."""
    from repro.core import model as m
    ab = m.abstract_params(cfg, plan)
    import jax
    total = 0.0
    pspecs = m.param_pspecs(cfg, plan)
    for leaf, spec in zip(jax.tree_util.tree_leaves(ab),
                          jax.tree_util.tree_leaves(
                              pspecs, is_leaf=lambda x: isinstance(
                                  x, type(jax.sharding.PartitionSpec()))), strict=True):
        nb = float(np.prod(leaf.shape)) * leaf.dtype.itemsize
        if len(spec) and spec[0] == "model" or \
                (len(spec) > 1 and spec[1] == "model"):
            nb /= plan.tp
        total += nb
    return total


def model_flops_ideal(cfg: ModelConfig, shape: ShapeConfig) -> float:
    """The classic 6*N*D (train) / 2*N*D (inference) + exact attention term,
    GLOBAL (all devices).  N = active params."""
    n_active = active_param_count(cfg)
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        base = 6.0 * n_active * tokens
        attn = 3.0 * attn_flops_ideal(cfg, shape.global_batch, shape.seq_len)
    elif shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        base = 2.0 * n_active * tokens
        attn = attn_flops_ideal(cfg, shape.global_batch, shape.seq_len)
    else:
        tokens = shape.global_batch
        base = 2.0 * n_active * tokens
        attn = decode_attn_flops_ideal(cfg, shape.global_batch, shape.seq_len)
    return base + attn


def attn_flops_ideal(cfg, B, S):
    total = 0.0
    for spec in cfg.layer_specs() + (cfg.encoder_layer_specs()
                                     if cfg.is_encdec else []):
        if spec.mixer not in (MIX_ATTN, MIX_HYBRID):
            continue
        span = min(S, cfg.sliding_window) if spec.attn == ATTN_WINDOW and \
            cfg.sliding_window else S
        eff = S * span if spec.attn == ATTN_WINDOW else S * S / 2
        total += 2.0 * B * cfg.n_heads * eff * cfg.head_dim_ * 2
    return total


def decode_attn_flops_ideal(cfg, B, kv_len):
    total = 0.0
    for spec in cfg.layer_specs():
        if spec.mixer not in (MIX_ATTN, MIX_HYBRID):
            continue
        span = min(kv_len, cfg.sliding_window) if spec.attn == ATTN_WINDOW \
            and cfg.sliding_window else kv_len
        total += 2.0 * B * cfg.n_heads * span * cfg.head_dim_ * 2
    return total


def active_param_count(cfg: ModelConfig) -> float:
    """Params touched per token (MoE: top_k + shared experts only)."""
    from repro.core.model import param_count
    total = param_count(cfg)
    if not cfg.n_experts:
        return total
    nmat = 3 if cfg.gated_ffn else 2
    per_expert = nmat * cfg.d_model * cfg.moe_d_ff
    n_moe_layers = sum(1 for s in cfg.layer_specs() if s.ffn == FFN_MOE)
    inactive = (cfg.n_experts - cfg.top_k) * per_expert * n_moe_layers
    return total - inactive
