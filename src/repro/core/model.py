"""Composable transformer: parameter templates, sharding, forward passes.

Single source of truth is ``model_template(cfg, plan)``: a pytree of
``ParamSpec(kind, full_shape, init)`` describing every parameter's canonical
(unsharded, unpadded) shape.  From it we derive:

* ``init_params``     — deterministic canonical init + ``shard_full`` scatter
                        (so tp=1 and tp=N initializations are bit-identical
                        up to layout: the TP-equivalence tests rely on this),
* ``abstract_params`` — ShapeDtypeStructs for the 512-device dry-run,
* ``param_pspecs``    — PartitionSpecs for shard_map in_specs,
* ``param_count``     — exact parameter count.

Forward passes are written per-shard (called inside shard_map) and run the
layer stack as ``lax.scan`` over stacked layer-group params (bounded compile
time at depth 88+); the CommLedger multiplier makes scanned collectives
count exactly n_reps times.
"""
from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs.base import (FFN_DENSE, FFN_MOE, FFN_NONE,
                                MIX_ATTN, MIX_HYBRID, MIX_NONE, MIX_SSM,
                                ModelConfig)
from repro.core import collectives as cc
from repro.core.blocks import _lo, layer_forward, tp_index
from repro.core.layers import apply_norm, sharded_embed, sharded_logits, \
    sharded_xent
from repro.core.partition import ModelLayout, ShardingPlan, dim_layout, \
    model_layout


# ---------------------------------------------------------------------------
# Param specs
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class ParamSpec:
    kind: str                 # sharding kind (see shard_full)
    full: tuple               # canonical full shape
    init: str = "normal"      # normal | zeros | ones | a_log | dt_bias
    scale: float = 0.02
    ffn_dim: int = 0          # per-layer F (dense layers with overrides)
    dtype: str = ""           # held in this dtype whatever the weights'

    @property
    def is_leaf(self):
        return True


def _is_spec(x):
    return isinstance(x, ParamSpec)


def _norm_t(cfg):
    if cfg.norm == "layernorm":
        return {"scale": ParamSpec("replicated", (cfg.d_model,), "ones"),
                "bias": ParamSpec("replicated", (cfg.d_model,), "zeros")}
    return {"scale": ParamSpec("replicated", (cfg.d_model,), "zeros")}


def _attn_t(cfg, n_layers_total):
    E, d = cfg.d_model, cfg.head_dim_
    Hq, Hkv = cfg.n_heads, cfg.n_kv_heads
    out_scale = 0.02 / math.sqrt(2 * n_layers_total)
    t = {
        "wq": ParamSpec("col_heads", (E, Hq, d)),
        "wk": ParamSpec("kv_heads", (E, Hkv, d)),
        "wv": ParamSpec("kv_heads", (E, Hkv, d)),
        "wo": ParamSpec("row_heads", (Hq, d, E), scale=out_scale),
    }
    if cfg.qk_norm:
        t["q_norm"] = ParamSpec("replicated", (d,), "zeros")
        t["k_norm"] = ParamSpec("replicated", (d,), "zeros")
    return t


def _ssm_t(cfg, n_layers_total):
    E = cfg.d_model
    Pd, N, H = cfg.ssm_head_dim, cfg.ssm_state, cfg.ssm_heads_
    GN = cfg.ssm_groups * N               # B and C of every group
    K = cfg.ssm_conv
    out_scale = 0.02 / math.sqrt(2 * n_layers_total)
    t = {
        "in_z": ParamSpec("ssm_col_heads", (E, H, Pd)),
        "in_x": ParamSpec("ssm_col_heads", (E, H, Pd)),
        "in_dt": ParamSpec("ssm_col_head_vec", (E, H)),
        "in_B": ParamSpec("replicated", (E, GN)),
        "in_C": ParamSpec("replicated", (E, GN)),
        "conv_x": ParamSpec("ssm_conv_heads", (H, Pd, K), scale=0.2),
        "conv_B": ParamSpec("replicated", (GN, K), scale=0.2),
        "conv_C": ParamSpec("replicated", (GN, K), scale=0.2),
        "A_log": ParamSpec("ssm_head_vec", (H,), "a_log"),
        "D": ParamSpec("ssm_head_vec", (H,), "ones"),
        "dt_bias": ParamSpec("ssm_head_vec", (H,), "dt_bias"),
        "norm_scale": ParamSpec("ssm_flat_heads", (H, Pd), "zeros"),
        "out": ParamSpec("ssm_row_heads", (H, Pd, E), scale=out_scale),
    }
    if cfg.ssm_conv_bias:
        t["conv_x_bias"] = ParamSpec("ssm_flat_heads", (H, Pd), "zeros")
        t["conv_B_bias"] = ParamSpec("replicated", (GN,), "zeros")
        t["conv_C_bias"] = ParamSpec("replicated", (GN,), "zeros")
    return t


def _dense_ffn_t(cfg, d_ff, n_layers_total):
    E = cfg.d_model
    out_scale = 0.02 / math.sqrt(2 * n_layers_total)
    t = {"w_up": ParamSpec("col_dim", (E, d_ff), ffn_dim=d_ff),
         "w_down": ParamSpec("row_dim", (d_ff, E), scale=out_scale,
                             ffn_dim=d_ff)}
    if cfg.gated_ffn:
        t["w_gate"] = ParamSpec("col_dim", (E, d_ff), ffn_dim=d_ff)
    return t


def _moe_ffn_t(cfg, n_layers_total):
    """The router spans every expert; the layer holds ``experts_held_``."""
    E = cfg.d_model
    F, n = cfg.moe_d_ff, cfg.experts_held_
    out_scale = 0.02 / math.sqrt(2 * n_layers_total)
    ex = {"w_up": ParamSpec("moe_col", (n, E, F)),
          "w_down": ParamSpec("moe_row", (n, F, E), scale=out_scale)}
    if cfg.gated_ffn:
        ex["w_gate"] = ParamSpec("moe_col", (n, E, F))
    router = {"w": ParamSpec("replicated", (E, cfg.n_experts))}
    if cfg.router == "sigmoid":     # float32, as the sigmoid router is held
        router = {"w": ParamSpec("replicated", (E, cfg.n_experts),
                                 dtype="float32"),
                  "bias": ParamSpec("replicated", (cfg.n_experts,), "zeros",
                                    dtype="float32")}
    t = {"router": router, "experts": ex}
    if cfg.n_shared_experts:
        t["shared"] = _dense_ffn_t(cfg, cfg.shared_d_ff_, n_layers_total)
    return t


def layer_template(cfg, spec, n_layers_total):
    t = {"ln1": _norm_t(cfg)} if spec.mixer != MIX_NONE else {}
    if spec.mixer in (MIX_ATTN, MIX_HYBRID):
        t["attn"] = _attn_t(cfg, n_layers_total)
    if spec.mixer in (MIX_SSM, MIX_HYBRID):
        t["ssm"] = _ssm_t(cfg, n_layers_total)
    if cfg.sandwich_norm:
        t["post_ln1"] = _norm_t(cfg)
    if spec.cross_attn:
        t["ln_x"] = _norm_t(cfg)
        t["xattn"] = _attn_t(cfg, n_layers_total)
    if spec.ffn == FFN_DENSE:
        t["ln2"] = _norm_t(cfg)
        t["ffn"] = _dense_ffn_t(cfg, spec.d_ff, n_layers_total)
    elif spec.ffn == FFN_MOE:
        t["ln2"] = _norm_t(cfg)
        t["ffn"] = _moe_ffn_t(cfg, n_layers_total)
    if cfg.sandwich_norm and spec.ffn != FFN_NONE:
        t["post_ln2"] = _norm_t(cfg)
    return t


def model_template(cfg: ModelConfig):
    E, V = cfg.d_model, cfg.vocab_size
    nl = cfg.n_layers + cfg.n_enc_layers
    t = {
        "embed": {"table": ParamSpec("vocab", (V, E), scale=0.02)},
        "stacks": [[layer_template(cfg, s, nl) for s in g.pattern]
                   for g in cfg.layer_groups()],
        "final_norm": _norm_t(cfg),
    }
    if not cfg.tie_embeddings:
        t["lm_head"] = {"w": ParamSpec("vocab", (V, E))}
    if cfg.is_encdec:
        t["encoder"] = {
            "stacks": [[layer_template(cfg, s, nl) for s in g.pattern]
                       for g in cfg.layer_groups(cfg.encoder_layer_specs())],
            "final_norm": _norm_t(cfg),
        }
    return t


def param_count(cfg: ModelConfig) -> int:
    tmpl = model_template(cfg)
    total = 0

    def walk(node, reps=1):
        nonlocal total
        if _is_spec(node):
            total += reps * int(np.prod(node.full))
            return
        if isinstance(node, dict):
            for v in node.values():
                walk(v, reps)
        elif isinstance(node, list):
            for v in node:
                walk(v, reps)

    for key, val in tmpl.items():
        if key == "stacks":
            for g, sub in zip(cfg.layer_groups(), val, strict=True):
                for pat_t in sub:
                    walk(pat_t, g.n_reps)
        elif key == "encoder":
            for g, sub in zip(cfg.layer_groups(cfg.encoder_layer_specs()),
                              val["stacks"], strict=True):
                for pat_t in sub:
                    walk(pat_t, g.n_reps)
            walk(val["final_norm"])
        else:
            walk(val)
    return total


# ---------------------------------------------------------------------------
# Sharding of canonical tensors (scatter) — numpy/jnp, deterministic
# ---------------------------------------------------------------------------

def shard_full(spec: ParamSpec, full, cfg, plan: ShardingPlan,
               lay: ModelLayout):
    """Canonical full tensor -> sharded layout with leading tp axis."""
    kind, tp = spec.kind, plan.tp
    if kind == "replicated":
        return full
    hl = lay.ssm if kind.startswith("ssm_") else lay.attn
    k = kind[4:] if kind.startswith("ssm_") else kind

    def pad_axis(x, axis, to):
        padw = [(0, 0)] * x.ndim
        padw[axis] = (0, to - x.shape[axis])
        return jnp.pad(x, padw) if to > x.shape[axis] else x

    if k == "col_heads":      # (E,H,D) -> (tp, E, hq_loc, D)
        x = pad_axis(full, 1, hl.hq_pad)
        x = x.reshape(x.shape[0], tp, hl.hq_loc, x.shape[2])
        return jnp.moveaxis(x, 1, 0)
    if k == "col_head_vec":   # (E,H) -> (tp, E, hq_loc)
        x = pad_axis(full, 1, hl.hq_pad)
        return jnp.moveaxis(x.reshape(x.shape[0], tp, hl.hq_loc), 1, 0)
    if k == "row_heads":      # (H,D,E) -> (tp, hq_loc, D, E)
        x = pad_axis(full, 0, hl.hq_pad)
        return x.reshape(tp, hl.hq_loc, x.shape[1], x.shape[2])
    if k == "head_vec":       # (H,) -> (tp, hq_loc)
        return pad_axis(full, 0, hl.hq_pad).reshape(tp, hl.hq_loc)
    if k == "flat_heads":     # (H,P) -> (tp, hq_loc*P)
        x = pad_axis(full, 0, hl.hq_pad)
        return x.reshape(tp, hl.hq_loc * x.shape[1])
    if k == "conv_heads":     # (H,P,K) -> (tp, hq_loc, P, K)
        x = pad_axis(full, 0, hl.hq_pad)
        return x.reshape(tp, hl.hq_loc, x.shape[1], x.shape[2])
    if k == "kv_heads":       # (E,n_kv,D) -> gather kv_map -> (tp,E,n_kv_loc,D)
        kvm = np.asarray(hl.kv_map)                    # (tp, n_kv_loc)
        x = jnp.take(full, jnp.asarray(kvm.reshape(-1)), axis=1)
        x = x.reshape(full.shape[0], tp, hl.n_kv_loc, full.shape[2])
        return jnp.moveaxis(x, 1, 0)
    if k == "col_dim":        # (E,F) -> (tp, E, f_loc)
        dl = dim_layout(full.shape[1], tp)
        x = pad_axis(full, 1, dl.n_pad)
        return jnp.moveaxis(x.reshape(x.shape[0], tp, dl.loc), 1, 0)
    if k == "row_dim":        # (F,E) -> (tp, f_loc, E)
        dl = dim_layout(full.shape[0], tp)
        x = pad_axis(full, 0, dl.n_pad)
        return x.reshape(tp, dl.loc, x.shape[1])
    if k == "vocab":          # (V,E) -> (tp, v_loc, E)
        dl = lay.vocab
        x = pad_axis(full, 0, dl.n_pad)
        return x.reshape(tp, dl.loc, x.shape[1])
    if k == "moe_col":        # (n_exp,E,F)
        if plan.moe_mode == "ep":
            return full.reshape(tp, full.shape[0] // tp, *full.shape[1:])
        dl = dim_layout(full.shape[2], tp)
        x = pad_axis(full, 2, dl.n_pad)
        x = x.reshape(*x.shape[:2], tp, dl.loc)
        return jnp.moveaxis(x, 2, 0)
    if k == "moe_row":        # (n_exp,F,E)
        if plan.moe_mode == "ep":
            return full.reshape(tp, full.shape[0] // tp, *full.shape[1:])
        dl = dim_layout(full.shape[1], tp)
        x = pad_axis(full, 1, dl.n_pad)
        x = x.reshape(x.shape[0], tp, dl.loc, x.shape[2])
        return jnp.moveaxis(x, 1, 0)
    raise ValueError(kind)


def _mask_invalid_heads(spec, sharded, cfg, plan, lay):
    """Zero the q-padding slots so padded heads contribute exactly 0."""
    kind = spec.kind
    hl = lay.ssm if kind.startswith("ssm_") else lay.attn
    k = kind[4:] if kind.startswith("ssm_") else kind
    if k not in ("col_heads", "row_heads", "col_head_vec"):
        return sharded
    valid = jnp.asarray(np.asarray(hl.q_valid))          # (tp, hq_loc)
    if k == "col_heads":
        return sharded * valid[:, None, :, None]
    if k == "col_head_vec":
        return sharded * valid[:, None, :]
    return sharded * valid[:, :, None, None]             # row_heads


def sharded_shape(spec: ParamSpec, cfg, plan, lay):
    fake = jax.eval_shape(
        lambda: shard_full(spec, jnp.zeros(spec.full, jnp.bfloat16), cfg,
                           plan, lay))
    return fake.shape


# ---------------------------------------------------------------------------
# Template -> (abstract params, pspecs, init)
# ---------------------------------------------------------------------------

def _map_template(tmpl, fn_spec, reps_stack=None):
    """Map over template leaves; ``stacks`` entries get a leading reps dim."""
    def walk(node, reps):
        if _is_spec(node):
            return fn_spec(node, reps)
        if isinstance(node, dict):
            return {k: walk(v, reps) for k, v in node.items()}
        if isinstance(node, list):
            return [walk(v, reps) for v in node]
        raise TypeError(type(node))

    out = {}
    for key, val in tmpl.items():
        if key == "stacks":
            out[key] = [ [walk(pt, rep) for pt in sub]
                         for rep, sub in val ]
        elif key == "encoder":
            out[key] = {
                "stacks": [[walk(pt, rep) for pt in sub]
                           for rep, sub in val["stacks"]],
                "final_norm": walk(val["final_norm"], 0),
            }
        else:
            out[key] = walk(val, 0)
    return out


def _with_reps(cfg, tmpl):
    """Pair each stacks entry with its group rep count (helper for mapping)."""
    t = dict(tmpl)
    t["stacks"] = list(zip([g.n_reps for g in cfg.layer_groups()],
                           tmpl["stacks"], strict=True))
    if "encoder" in tmpl:
        enc_groups = cfg.layer_groups(cfg.encoder_layer_specs())
        t["encoder"] = dict(tmpl["encoder"])
        t["encoder"]["stacks"] = list(zip([g.n_reps for g in enc_groups],
                                          tmpl["encoder"]["stacks"], strict=True))
    return t


def abstract_params(cfg, plan, dtype=None):
    lay = model_layout(cfg, plan)
    dt = jnp.dtype(dtype or plan.weight_dtype or cfg.dtype)

    def mk(spec, reps):
        shape = sharded_shape(spec, cfg, plan, lay)
        if reps:
            shape = (reps,) + shape
        if spec.dtype:
            d = jnp.dtype(spec.dtype)
        elif spec.init in ("a_log", "dt_bias"):
            d = jnp.float32
        elif spec.kind == "replicated":
            d = jnp.dtype(cfg.dtype)     # norms/routers stay high precision
        else:
            d = dt
        return jax.ShapeDtypeStruct(shape, d)

    return _map_template(_with_reps(cfg, model_template(cfg)), mk)


def _param_pspec(spec, reps, cfg, plan, lay):
    if spec.kind == "replicated":
        base = P(*([None] * len(spec.full)))
    else:
        tpax = plan.tp_axis if plan.tp > 1 else None
        ndim = len(sharded_shape(spec, cfg, plan, lay))
        base = P(*([tpax] + [None] * (ndim - 1)))
    if reps:
        base = P(*((None,) + tuple(base)))
    return base


def param_pspecs(cfg, plan):
    lay = model_layout(cfg, plan)
    return _map_template(_with_reps(cfg, model_template(cfg)),
                         lambda spec, reps: _param_pspec(spec, reps, cfg,
                                                         plan, lay))


def init_params(cfg, plan, seed=0, dtype=None, mesh=None):
    """Deterministic init: canonical full tensors (independent of plan),
    then scatter.  Without ``mesh`` the leaves are built eagerly on the
    default device (fine for reduced/paper models).  With ``mesh`` each
    leaf is computed under ``jit`` straight into its ``NamedSharding``
    (``param_pspecs``), so a multi-chip mesh never stages the whole model
    on one chip; the values are the same either way."""
    lay = model_layout(cfg, plan)
    dt = jnp.dtype(dtype or cfg.dtype)
    counter = [0]

    def mk(spec, reps):
        keys = []
        for _ in range(max(reps, 1)):
            counter[0] += 1
            keys.append(jax.random.fold_in(jax.random.PRNGKey(seed),
                                           counter[0]))
        keep_f32 = spec.init in ("a_log", "dt_bias")
        leaf_dt = jnp.dtype(spec.dtype) if spec.dtype else \
            (jnp.float32 if keep_f32 else dt)

        def build(keys):
            leaves = []
            for key in keys:
                sh = shard_full(spec, _init_full(spec, key), cfg, plan, lay)
                sh = _mask_invalid_heads(spec, sh, cfg, plan, lay)
                leaves.append(sh.astype(leaf_dt))
            return jnp.stack(leaves) if reps else leaves[0]

        if mesh is None:
            return build(keys)
        sharding = NamedSharding(mesh, _param_pspec(spec, reps, cfg, plan,
                                                    lay))
        return jax.jit(build, out_shardings=sharding)(keys)

    return _map_template(_with_reps(cfg, model_template(cfg)), mk)


def _init_full(spec: ParamSpec, key):
    if spec.init == "zeros":
        return jnp.zeros(spec.full, jnp.float32)
    if spec.init == "ones":
        return jnp.ones(spec.full, jnp.float32)
    if spec.init == "a_log":
        n = spec.full[0]
        return jnp.log(jnp.linspace(1.0, 16.0, n))
    if spec.init == "dt_bias":
        n = spec.full[0]
        dts = jnp.exp(jnp.linspace(math.log(1e-3), math.log(0.1), n))
        return jnp.log(jnp.expm1(dts))            # inverse softplus
    return spec.scale * jax.random.normal(key, spec.full, jnp.float32)


# ---------------------------------------------------------------------------
# Forward passes (per-shard; call inside shard_map)
# ---------------------------------------------------------------------------

def _run_stack(x, stack_params, groups, cfg, plan, lay, mode, positions,
               pos=None, enc_memory=None, cache=None, causal_specs=None):
    """Scan every layer group.  cache: list aligned with groups (or None)."""
    new_cache = [] if cache is not None else None
    for gi, (group, gparams) in enumerate(zip(groups, stack_params, strict=True)):
        gcache = cache[gi] if cache is not None else None

        def body(xc, per_rep, group=group):   # bind the loop var (B023)
            p_rep, c_rep = per_rep
            nc_rep = []
            for pi, spec in enumerate(group.pattern):
                ci = c_rep[pi] if c_rep is not None else None
                xc, nc, _ = layer_forward(xc, p_rep[pi], ci, cfg, plan, lay,
                                          spec, mode, positions, pos,
                                          enc_memory)
                nc_rep.append(nc if nc is not None else {})
            return xc, (nc_rep if c_rep is not None else None)

        if mode == "train" and plan.remat == "block":
            body = jax.checkpoint(body)
        elif mode == "train" and plan.remat == "selective":
            # save matmul outputs, recompute only elementwise ops
            body = jax.checkpoint(
                body,
                policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)

        with cc.LEDGER.scaled(group.n_reps):
            if group.n_reps == 1:
                p_rep = jax.tree_util.tree_map(lambda a: a[0], gparams)
                c_rep = (jax.tree_util.tree_map(lambda a: a[0], gcache)
                         if gcache is not None else None)
                x, nc = body(x, (p_rep, c_rep))
                nc = (jax.tree_util.tree_map(lambda a: a[None], nc)
                      if nc is not None else None)
            else:
                x, nc = jax.lax.scan(body, x, (gparams, gcache))
        if new_cache is not None:
            new_cache.append(nc)
    return x, new_cache


def counts_expert_picks(cfg) -> bool:
    """True when the paged steps also return each row's count of (expert
    layer, pick) pairs that fell on the experts this chip holds: models
    that have expert layers."""
    return any(s.ffn == FFN_MOE for s in cfg.layer_specs())


def _run_paged_stack(x, stack_params, groups, cfg, plan, lay, mode,
                     positions, pos, cache, pages):
    """The paged steps' layer loop (decode, verify, prefill chunk).

    Each group's stacked pools (pages, their scales, SSM slabs, cross
    pages) are the loop's carried state, whole: layer ``li`` reads and
    writes only its own rows of them by index (``pages["layer"]``), so the
    donated pools are updated in place and no layer's pool is sliced out
    or re-stacked.  Scanning them as ``xs``/``ys`` instead makes the
    compiled step allocate and copy whole pools.

    -> (x, cache, picks): ``picks`` (B, S) counts each position's (expert
    layer, pick) pairs on held experts where ``counts_expert_picks``,
    else None."""
    new_cache = []
    picks = jnp.zeros(positions.shape, jnp.int32) \
        if counts_expert_picks(cfg) else None
    for group, gparams, gcache in zip(groups, stack_params, cache,
                                      strict=True):

        def body(carry, per_rep, group=group):   # bind the loop var (B023)
            xc, pools, pk = carry
            p_rep, li = per_rep
            layer_pages = dict(pages, layer=li)
            new_pools = []
            for pi, spec in enumerate(group.pattern):
                xc, nc, lpk = layer_forward(xc, p_rep[pi], pools[pi], cfg,
                                            plan, lay, spec, mode, positions,
                                            pos, None, layer_pages)
                new_pools.append(nc if nc is not None else {})
                if lpk is not None:
                    pk = pk + lpk
            return (xc, new_pools, pk), None

        with cc.LEDGER.scaled(group.n_reps):
            if group.n_reps == 1:
                p_rep = jax.tree_util.tree_map(lambda a: a[0], gparams)
                (x, gcache, picks), _ = body((x, gcache, picks), (p_rep, 0))
            else:
                (x, gcache, picks), _ = jax.lax.scan(
                    body, (x, gcache, picks),
                    (gparams, jnp.arange(group.n_reps, dtype=jnp.int32)))
        new_cache.append(gcache)
    return x, new_cache, picks


def _with_picks(out, picks):
    """A paged step's first output: ``out`` alone, or ``(out, picks)``
    for a model whose steps count expert picks (``counts_expert_picks``)."""
    return out if picks is None else (out, picks)


def embed_tokens(params, tokens, cfg, plan, lay):
    emb = sharded_embed(tokens, _lo(params["embed"]["table"]),
                        tp_index(plan), lay.vocab.loc, plan.tp_axes)
    if cfg.scale_embed:
        emb = emb * jnp.asarray(math.sqrt(cfg.d_model), emb.dtype)
    return emb


def final_logits(params, x, cfg, lay):
    head = params.get("lm_head", {}).get("w", params["embed"]["table"])
    return sharded_logits(x, _lo(head))


def encode(params, frames, cfg, plan, lay):
    """Encoder stack over precomputed frame embeddings (audio stub)."""
    groups = cfg.layer_groups(cfg.encoder_layer_specs())
    pos = jnp.broadcast_to(jnp.arange(frames.shape[1]), frames.shape[:2])
    x, _ = _run_stack(frames, params["encoder"]["stacks"], groups, cfg, plan,
                      lay, "train", pos)
    return apply_norm(x, params["encoder"]["final_norm"], cfg)


def forward_cross_kv(params, enc_memory, cfg, plan, lay):
    """Every cross-attention layer's K/V of the encoder memory.

    -> list aligned with ``cfg.layer_groups()``: per group, per pattern
    entry, either None (no cross-attention) or ``{"k", "v"}`` of shape
    (reps, B, G, S_enc, D) — the grouped-GQA layout ``cross_attn_mixer``
    attends over.  Computed once per encode; the paged serving path
    scatters these into the cross page pools
    (``steps.make_cross_kv_write_step``), after which they are immutable.
    """
    from repro.core.layers import rmsnorm

    def one_layer(pa):
        k = jnp.einsum("bse,ehd->bshd", enc_memory, _lo(pa["wk"]))
        v = jnp.einsum("bse,ehd->bshd", enc_memory, _lo(pa["wv"]))
        if cfg.qk_norm:
            k = rmsnorm(k, pa["k_norm"], cfg.norm_eps)
        return {"k": k.swapaxes(1, 2), "v": v.swapaxes(1, 2)}

    out = []
    for group, gparams in zip(cfg.layer_groups(), params["stacks"], strict=True):
        per_pat = []
        for pi, spec in enumerate(group.pattern):
            if not spec.cross_attn:
                per_pat.append(None)
                continue
            per_pat.append(jax.vmap(one_layer)(gparams[pi]["xattn"]))
        out.append(per_pat)
    return out


def _cp_positions(B, S, plan):
    """Absolute positions for this shard's sequence slice (context parallel:
    the local S is a contiguous slice at offset cp_index * S)."""
    off = 0
    if plan.cp_axes:
        from repro.core.blocks import dp_linear_index
        off = dp_linear_index(plan.cp_axes) * S
    return jnp.broadcast_to(off + jnp.arange(S), (B, S))


def forward_train(params, batch, cfg, plan, lay):
    """-> mean NLL (per-shard scalar; psum'd over dp axes by the caller)."""
    tokens, labels = batch["tokens"], batch["labels"]
    B, S = tokens.shape
    positions = _cp_positions(B, S, plan)
    x = embed_tokens(params, tokens, cfg, plan, lay)
    if cfg.frontend == "vision_patches" and "image_embeds" in batch:
        n = batch["image_embeds"].shape[1]
        x = jnp.concatenate([batch["image_embeds"].astype(x.dtype),
                             x[:, n:]], axis=1)
    enc_memory = None
    if cfg.is_encdec:
        enc_memory = encode(params, batch["frames"].astype(x.dtype), cfg,
                            plan, lay)
    groups = cfg.layer_groups()
    x, _ = _run_stack(x, params["stacks"], groups, cfg, plan, lay, "train",
                      positions, enc_memory=enc_memory)
    x = apply_norm(x, params["final_norm"], cfg)
    if cfg.is_encoder_only:
        # masked-token style objective: predict every position's token
        labels = tokens
    logits = final_logits(params, x, cfg, lay)
    nll = sharded_xent(logits, labels, tp_index(plan), lay.vocab.loc,
                       cfg.vocab_size, plan.tp_axes)
    return jnp.mean(nll)


def forward_prefill(params, tokens_or_frames, cache0, cfg, plan, lay,
                    extra=None):
    """Prefill: run full prompt, fill the cache.  -> (last_logits, cache)."""
    extra = extra or {}
    if cfg.is_encdec:
        frames = tokens_or_frames            # (B, S, E) stub embeddings
        enc_memory = encode(params, frames.astype(jnp.dtype(cfg.dtype)),
                            cfg, plan, lay)
        tokens = extra["dec_tokens"]
    else:
        enc_memory = None
        tokens = tokens_or_frames
    B, S = tokens.shape
    positions = _cp_positions(B, S, plan)
    x = embed_tokens(params, tokens, cfg, plan, lay)
    if cfg.frontend == "vision_patches" and "image_embeds" in extra:
        n = extra["image_embeds"].shape[1]
        x = jnp.concatenate([extra["image_embeds"].astype(x.dtype),
                             x[:, n:]], axis=1)
    groups = cfg.layer_groups()
    x, cache = _run_stack(x, params["stacks"], groups, cfg, plan, lay,
                          "prefill", positions, enc_memory=enc_memory,
                          cache=cache0)
    x = apply_norm(x[:, -1:], params["final_norm"], cfg)
    logits = final_logits(params, x, cfg, lay)[:, 0]
    if plan.cp_axes and cc.axis_size(plan.cp_axes) > 1:
        # the true last token lives on the last CP shard: masked broadcast
        from repro.core.blocks import dp_linear_index
        n_cp = cc.axis_size(plan.cp_axes)
        last = dp_linear_index(plan.cp_axes) == n_cp - 1
        logits = cc.psum(jnp.where(last, logits, jnp.zeros_like(logits)),
                         plan.cp_axes, "prefill/cp_logits")
    return logits, cache


def forward_decode(params, cache, tokens, pos, cfg, plan, lay, pages=None):
    """One decode step.  tokens: (B, 1); pos: (B,) -> (logits, cache)."""
    positions = pos[:, None]
    x = embed_tokens(params, tokens, cfg, plan, lay)
    groups = cfg.layer_groups()
    picks = None
    if pages is None:
        x, cache = _run_stack(x, params["stacks"], groups, cfg, plan, lay,
                              "decode", positions, pos=pos, cache=cache)
    else:
        x, cache, picks = _run_paged_stack(x, params["stacks"], groups, cfg,
                                           plan, lay, "decode", positions,
                                           pos, cache, pages)
    x = apply_norm(x, params["final_norm"], cfg)
    logits = final_logits(params, x, cfg, lay)[:, 0]
    return _with_picks(logits, None if picks is None else picks[:, 0]), \
        cache


def forward_verify(params, cache, tokens, pos, qlen, cfg, plan, lay, pages):
    """Speculative verify: score Q consecutive positions per slot at once.

    tokens: (B, Q) — column 0 is the slot's last accepted token, columns
    1..Q-1 are drafted continuations; pos: (B,) absolute position of
    column 0; qlen: (B,) live columns per row (columns >= qlen are
    padding — their positions are set to -1 so their KV lands on the
    scratch page and their logits are garbage the caller ignores).
    -> (logits (B, Q, V_loc), cache): row i is the next-token distribution
    after consuming tokens[:, :i+1] — token-equivalent to feeding them to
    ``forward_decode`` one at a time, in one fused pass over the cache.
    """
    B, Q = tokens.shape
    positions = pos[:, None] + jnp.broadcast_to(jnp.arange(Q), (B, Q))
    positions = jnp.where(jnp.arange(Q)[None, :] < qlen[:, None],
                          positions, -1)
    x = embed_tokens(params, tokens, cfg, plan, lay)
    groups = cfg.layer_groups()
    x, cache, _ = _run_paged_stack(x, params["stacks"], groups, cfg, plan,
                                   lay, "verify", positions, pos, cache,
                                   pages)
    x = apply_norm(x, params["final_norm"], cfg)
    logits = final_logits(params, x, cfg, lay)
    return logits, cache


def forward_prefill_chunk(params, cache, tokens, chunk_start, last_idx, cfg,
                          plan, lay, pages):
    """One fixed-size prefill chunk against the paged cache.

    tokens: (B, C) chunk of the prompt (zero-padded past its end);
    chunk_start: () absolute position of the chunk's first token;
    last_idx: () in-chunk index of the prompt's final token (only meaningful
    on the chunk that contains it — callers use the returned logits then).
    -> (logits (B, V_loc), cache).  One compiled step serves every prompt
    length: length variation lives entirely in the (chunk_start, last_idx,
    block_table) inputs, never in shapes.
    """
    B, C = tokens.shape
    positions = chunk_start + jnp.broadcast_to(jnp.arange(C), (B, C))
    x = embed_tokens(params, tokens, cfg, plan, lay)
    groups = cfg.layer_groups()
    x, cache, picks = _run_paged_stack(x, params["stacks"], groups, cfg,
                                       plan, lay, "prefill", positions, None,
                                       cache, pages)
    x = jax.lax.dynamic_slice_in_dim(x, last_idx, 1, axis=1)
    x = apply_norm(x, params["final_norm"], cfg)
    logits = final_logits(params, x, cfg, lay)[:, 0]
    if picks is not None:           # the prompt's positions only
        picks = jnp.sum(jnp.where(jnp.arange(C) <= last_idx, picks, 0),
                        axis=1)
    return _with_picks(logits, picks), cache
