"""Jitted distributed entry points: train_step / prefill_step / decode_step.

These wrap the per-shard forwards from ``model.py`` in ``jax.shard_map``
with the paper's partitioning specs, then ``jax.jit``.  The same builders
serve CPU smoke tests (1-device mesh), the TP-equivalence tests (8 host
devices) and the 512-device production dry-run.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig, ShapeConfig
from repro.core import collectives as cc
from repro.core import kvcache, model
from repro.core.partition import ShardingPlan, model_layout
from repro.optim import AdamWConfig, adamw_update, init_opt_state


def mesh_axis_sizes(mesh):
    return dict(zip(mesh.axis_names, mesh.devices.shape, strict=True))


def prepare_ledger(mesh):
    cc.set_axis_sizes(mesh_axis_sizes(mesh))


def batch_axes(plan: ShardingPlan):
    return tuple(plan.dp_axes) if len(plan.dp_axes) > 1 else plan.dp_axes[0]


def n_dp(mesh, plan):
    sizes = mesh_axis_sizes(mesh)
    out = 1
    for a in plan.dp_axes:
        out *= sizes.get(a, 1)
    return out


def _shard_map(f, mesh, in_specs, out_specs):
    """``jax.shard_map`` keeps ``f``'s name and ``jax.jit`` names the
    compiled module after it, so each serving ``make_*_step`` names its
    per-shard body after the step (``paged_decode`` ->
    ``jit_paged_decode``): the name a profiler trace's ``XLA Modules``
    line and the compile listener's ``fun_name`` show."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


# ---------------------------------------------------------------------------
# Batch specs
# ---------------------------------------------------------------------------

def train_batch_template(cfg: ModelConfig, shape: ShapeConfig, plan):
    """-> (ShapeDtypeStructs, PartitionSpecs) for a global train batch."""
    B, S = shape.global_batch, shape.seq_len
    bt = batch_axes(plan)
    cp = tuple(plan.cp_axes) if plan.cp_axes else None
    t = {"tokens": jax.ShapeDtypeStruct((B, S), jnp.int32),
         "labels": jax.ShapeDtypeStruct((B, S), jnp.int32)}
    s = {"tokens": P(bt, cp), "labels": P(bt, cp)}
    if cfg.is_encdec:
        t["frames"] = jax.ShapeDtypeStruct((B, S, cfg.d_model), jnp.bfloat16)
        s["frames"] = P(bt, None, None)
    if cfg.frontend == "vision_patches":
        n = cfg.n_frontend_embeds
        t["image_embeds"] = jax.ShapeDtypeStruct((B, n, cfg.d_model),
                                                 jnp.bfloat16)
        s["image_embeds"] = P(bt, None, None)
    return t, s


# ---------------------------------------------------------------------------
# Train step
# ---------------------------------------------------------------------------

def make_train_step(cfg, plan, mesh, opt_cfg: Optional[AdamWConfig] = None,
                    shape: Optional[ShapeConfig] = None, grad_transform=None,
                    grad_accum: int = 1):
    """-> (train_step(state, batch) -> (state, metrics), specs dict).

    ``grad_accum`` > 1 splits the per-device batch into microbatches run
    under lax.scan with summed gradients — bounds activation memory for
    large models (the standard companion to selective remat; §Perf)."""
    prepare_ledger(mesh)
    lay = model_layout(cfg, plan)
    pspecs = model.param_pspecs(cfg, plan)
    opt_cfg = opt_cfg or AdamWConfig()
    sizes = mesh_axis_sizes(mesh)
    ndp = 1
    for a in plan.grad_axes:
        ndp *= sizes.get(a, 1)
    inner = ("data",)
    outer = ("pod",) if "pod" in mesh.axis_names else ()
    _, bspecs = train_batch_template(cfg, shape, plan) if shape else (None, None)

    def per_shard(params, batch):
        def loss_fn(p, mb):
            return model.forward_train(p, mb, cfg, plan, lay)

        if grad_accum > 1:
            micro = jax.tree_util.tree_map(
                lambda x: x.reshape((grad_accum, x.shape[0] // grad_accum)
                                    + x.shape[1:]), batch)

            def acc_step(carry, mb):
                loss_a, g_a = carry
                lv, g = jax.value_and_grad(loss_fn)(params, mb)
                return (loss_a + lv / grad_accum,
                        jax.tree_util.tree_map(
                            lambda a, b: a + b / grad_accum, g_a, g)), None

            zeros = jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)
            with cc.LEDGER.scaled(grad_accum):
                (loss, grads), _ = jax.lax.scan(
                    acc_step, (jnp.zeros((), jnp.float32), zeros), micro)
        else:
            loss, grads = jax.value_and_grad(
                lambda p: loss_fn(p, batch))(params)
        if grad_transform is not None:
            grads = grad_transform(grads)
        # hierarchical DP reduction (paper's grouped tree adapted to pods);
        # context-parallel shards also contribute gradients
        if plan.dp_hierarchical and outer and not plan.cp_axes:
            grads = cc.hierarchical_psum(grads, inner, outer, "dp/grads")
        else:
            grads = cc.psum(grads, plan.grad_axes, "dp/grads")
        grads = jax.tree_util.tree_map(lambda g: g / ndp, grads)
        loss = cc.psum(loss, plan.grad_axes, "dp/loss") / ndp
        return loss, grads

    if bspecs is None:
        bt = batch_axes(plan)
        bspecs = {"tokens": P(bt, None), "labels": P(bt, None)}

    sharded = _shard_map(per_shard, mesh, in_specs=(pspecs, bspecs),
                         out_specs=(P(), pspecs))

    def train_step(state, batch):
        loss, grads = sharded(state["params"], batch)
        new_p, new_opt, stats = adamw_update(state["params"], grads,
                                             state["opt"], opt_cfg)
        stats["loss"] = loss
        return {"params": new_p, "opt": new_opt}, stats

    return train_step, {"params": pspecs, "batch": bspecs}


# ---------------------------------------------------------------------------
# ZeRO-1: optimizer state sharded over the data axis
# ---------------------------------------------------------------------------
#
# The standard path replicates AdamW m/v (f32) across the data axis — 8x the
# bf16 param bytes per device (61.5 GB for mistral-large-123b at tp=16:
# untrainable on 16 GB HBM; see EXPERIMENTS §Perf H2).  ZeRO-1 instead:
#   1. reduce-scatters gradients over 'data' (flat, per leaf) — each data
#      shard owns 1/ndp of every gradient (wire: P(n-1)/n, HALF the psum),
#   2. updates its m/v/param chunk locally (f32 state: bytes / ndp),
#   3. all-gathers the updated bf16 params (wire: P(n-1)/n).
# Total wire == the old grad psum; optimizer memory and update bandwidth
# drop by ndp.  Cross-pod reduction of the (already 1/ndp) chunks keeps the
# paper's hierarchical structure.

def _z1_chunk(leaf_size: int, n: int) -> int:
    return (leaf_size + n - 1) // n


def _is_tp_leaf(spec) -> bool:
    return len(spec) > 0 and spec[0] == "model" or         (len(spec) > 1 and spec[1] == "model")


def abstract_train_state_zero1(cfg, plan, mesh):
    params = model.abstract_params(cfg, plan)
    pspecs = model.param_pspecs(cfg, plan)
    sizes = mesh_axis_sizes(mesh)
    nd = sizes.get("data", 1)

    def shard_leaf(p, spec):
        local = int(np.prod(p.shape))
        if _is_tp_leaf(spec):
            # local leaf excludes the tp axis
            local //= plan.tp
            shape = (plan.tp, nd, _z1_chunk(local, nd))
        else:
            shape = (nd, _z1_chunk(local, nd))
        return jax.ShapeDtypeStruct(shape, jnp.float32)

    flat = jax.tree_util.tree_map(
        shard_leaf, params, pspecs,
        is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))
    return {"params": params,
            "opt": {"m": flat, "v": flat,
                    "step": jax.ShapeDtypeStruct((), jnp.int32)}}


def train_state_pspecs_zero1(cfg, plan):
    pspecs = model.param_pspecs(cfg, plan)

    def spec_leaf(spec):
        if _is_tp_leaf(spec):
            return P("model", "data", None)
        return P("data", None)

    flat = jax.tree_util.tree_map(spec_leaf, pspecs,
                                  is_leaf=lambda x: isinstance(x, P))
    return {"params": pspecs,
            "opt": {"m": flat, "v": flat, "step": P()}}


def init_train_state_zero1(cfg, plan, mesh, seed=0):
    """Concrete ZeRO-1 state (small/reduced configs; big models restore)."""
    params = model.init_params(cfg, plan, seed)
    sizes = mesh_axis_sizes(mesh)
    nd = sizes.get("data", 1)
    pspecs = model.param_pspecs(cfg, plan)

    def zeros_leaf(p, spec):
        local = int(np.prod(p.shape))
        if _is_tp_leaf(spec):
            local //= plan.tp
            return jnp.zeros((plan.tp, nd, _z1_chunk(local, nd)), jnp.float32)
        return jnp.zeros((nd, _z1_chunk(local, nd)), jnp.float32)

    flat = jax.tree_util.tree_map(
        zeros_leaf, params, pspecs,
        is_leaf=lambda x: hasattr(x, "shape") and not isinstance(x, P))
    return {"params": params,
            "opt": {"m": flat, "v": flat, "step": jnp.zeros((), jnp.int32)}}


def make_train_step_zero1(cfg, plan, mesh,
                          opt_cfg: Optional[AdamWConfig] = None,
                          shape: Optional[ShapeConfig] = None,
                          grad_accum: int = 1):
    """ZeRO-1 train step: the whole update runs inside shard_map."""
    from repro.optim import adamw_leaf
    prepare_ledger(mesh)
    lay = model_layout(cfg, plan)
    pspecs = model.param_pspecs(cfg, plan)
    ospecs = train_state_pspecs_zero1(cfg, plan)
    opt_cfg = opt_cfg or AdamWConfig()
    sizes = mesh_axis_sizes(mesh)
    nd = sizes.get("data", 1)
    ndp = 1
    for a in plan.grad_axes:
        ndp *= sizes.get(a, 1)
    outer = ("pod",) if "pod" in mesh.axis_names else ()
    _, bspecs = train_batch_template(cfg, shape, plan) if shape else (None, None)
    if bspecs is None:
        bt = batch_axes(plan)
        bspecs = {"tokens": P(bt, None), "labels": P(bt, None)}
    flat_pspecs = jax.tree_util.tree_leaves(
        pspecs, is_leaf=lambda x: isinstance(x, P))

    def per_shard(params, opt, batch):
        def loss_fn(p, mb):
            return model.forward_train(p, mb, cfg, plan, lay)

        if grad_accum > 1:
            micro = jax.tree_util.tree_map(
                lambda x: x.reshape((grad_accum, x.shape[0] // grad_accum)
                                    + x.shape[1:]), batch)

            def acc_step(carry, mb):
                l_a, g_a = carry
                lv, g = jax.value_and_grad(loss_fn)(params, mb)
                return (l_a + lv / grad_accum,
                        jax.tree_util.tree_map(
                            lambda a, b: a + b / grad_accum, g_a, g)), None
            zeros = jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)
            with cc.LEDGER.scaled(grad_accum):
                (loss, grads), _ = jax.lax.scan(
                    acc_step, (jnp.zeros((), jnp.float32), zeros), micro)
        else:
            loss, grads = jax.value_and_grad(
                lambda p: loss_fn(p, batch))(params)
        loss = cc.psum(loss, plan.grad_axes, "dp/loss") / ndp

        flat_g = jax.tree_util.tree_leaves(grads)
        flat_p = jax.tree_util.tree_leaves(params)
        flat_m = jax.tree_util.tree_leaves(opt["m"])
        flat_v = jax.tree_util.tree_leaves(opt["v"])

        # 1) reduce-scatter grads over 'data' (+ cross-pod psum of chunks)
        g_chunks, p_chunks, tp_mask = [], [], []
        for g, p, spec in zip(flat_g, flat_p, flat_pspecs, strict=True):
            flat = g.reshape(-1).astype(jnp.float32)
            chunk = _z1_chunk(flat.shape[0], nd)
            pad = chunk * nd - flat.shape[0]
            if pad:
                flat = jnp.pad(flat, (0, pad))
            gs = cc.psum_scatter(flat, "data", "dp/z1_rs") if nd > 1 else flat
            if outer:
                gs = cc.psum(gs, outer, "dp/z1_xpod")
            g_chunks.append(gs / ndp)
            pf = p.reshape(-1).astype(jnp.float32)
            if pad:
                pf = jnp.pad(pf, (0, pad))
            if nd > 1:
                me = jax.lax.axis_index("data")
                pf = jax.lax.dynamic_slice_in_dim(pf, me * chunk, chunk)
            p_chunks.append(pf)
            tp_mask.append(_is_tp_leaf(spec))

        # 2) global grad norm (tp-sharded leaves differ across 'model';
        #    replicated leaves are identical there -> reduce separately)
        sq_tp = sum(jnp.sum(jnp.square(g)) for g, t in
                    zip(g_chunks, tp_mask, strict=True) if t) + 0.0
        sq_rep = sum(jnp.sum(jnp.square(g)) for g, t in
                     zip(g_chunks, tp_mask, strict=True) if not t) + 0.0
        sq_tp = cc.psum(sq_tp, ("data",) + tuple(plan.tp_axes) + outer,
                        "dp/z1_norm")
        sq_rep = cc.psum(sq_rep, ("data",) + outer, "dp/z1_norm")
        gnorm = jnp.sqrt(sq_tp + sq_rep)
        step = opt["step"] + 1
        scale = jnp.minimum(1.0, opt_cfg.clip_norm / (gnorm + 1e-9))
        lr = opt_cfg.lr * (opt_cfg.schedule(step) if opt_cfg.schedule
                           else 1.0)

        # 3) local chunk updates + 4) all-gather new params
        new_p_leaves, new_m, new_v = [], [], []
        for p, pc, gc, m, v in zip(flat_p, p_chunks, g_chunks,
                                   flat_m, flat_v, strict=True):
            np_, m2, v2 = adamw_leaf(pc, gc, m, v, step, scale, lr, opt_cfg)
            new_m.append(m2.reshape(m.shape))
            new_v.append(v2.reshape(v.shape))
            np_ = np_.reshape(-1).astype(p.dtype)   # bf16 on the wire
            full = cc.all_gather(np_, "data", "dp/z1_ag") if nd > 1 else np_
            new_p_leaves.append(full.reshape(-1)[: p.size].reshape(p.shape))

        tdef = jax.tree_util.tree_structure(params)
        new_params = jax.tree_util.tree_unflatten(tdef, new_p_leaves)
        new_opt = {"m": jax.tree_util.tree_unflatten(tdef, new_m),
                   "v": jax.tree_util.tree_unflatten(tdef, new_v),
                   "step": step}
        return loss, gnorm, new_params, new_opt

    # strip the leading (tp, nd) layout dims from opt specs for shard_map:
    # inside, each device sees its chunk directly
    sharded = _shard_map(
        per_shard, mesh,
        in_specs=(pspecs, ospecs["opt"], bspecs),
        out_specs=(P(), P(), pspecs, ospecs["opt"]))

    def train_step(state, batch):
        loss, gnorm, new_p, new_opt = sharded(state["params"], state["opt"],
                                              batch)
        return {"params": new_p, "opt": new_opt},             {"loss": loss, "grad_norm": gnorm}

    return train_step, {"params": pspecs, "batch": bspecs,
                        "opt": ospecs["opt"]}


def init_train_state(cfg, plan, seed=0):
    params = model.init_params(cfg, plan, seed)
    return {"params": params, "opt": init_opt_state(params)}


def abstract_train_state(cfg, plan):
    params = model.abstract_params(cfg, plan)
    def f32(p):
        return jax.ShapeDtypeStruct(p.shape, jnp.float32)

    return {"params": params,
            "opt": {"m": jax.tree_util.tree_map(f32, params),
                    "v": jax.tree_util.tree_map(f32, params),
                    "step": jax.ShapeDtypeStruct((), jnp.int32)}}


def train_state_pspecs(cfg, plan):
    pspecs = model.param_pspecs(cfg, plan)
    return {"params": pspecs,
            "opt": {"m": pspecs, "v": pspecs, "step": P()}}


# ---------------------------------------------------------------------------
# Serving steps
# ---------------------------------------------------------------------------

def _batch_sharded(plan, batch, mesh):
    """Whether a serving batch shards over the dp axes; tiny batches and
    sequence-sharded caches replicate it."""
    return batch % n_dp(mesh, plan) == 0 and not plan.seq_shard_kv


def serve_templates(cfg, plan, shape: ShapeConfig, mesh):
    """Abstract inputs + specs for prefill/decode lowering of one cell."""
    prepare_ledger(mesh)
    lay = model_layout(cfg, plan)
    B, S = shape.global_batch, shape.seq_len
    batch_ok = _batch_sharded(plan, B, mesh)
    bt = batch_axes(plan) if batch_ok else None
    tmpl = kvcache.cache_template(cfg, plan, lay, B, S,
                                  batch_sharded=batch_ok)
    cache = kvcache.abstract_cache(tmpl)
    cache_specs = kvcache.cache_pspecs(tmpl)
    t = {
        "tokens1": jax.ShapeDtypeStruct((B, 1), jnp.int32),
        "pos": jax.ShapeDtypeStruct((B,), jnp.int32),
        "cache": cache,
        "prompt": jax.ShapeDtypeStruct((B, S), jnp.int32),
    }
    cp = tuple(plan.cp_axes) if plan.cp_axes else None
    s = {
        "tokens1": P(bt, None),
        "pos": P(bt),
        "cache": cache_specs,
        "prompt": P(bt, cp),
    }
    if cfg.is_encdec:
        # frames span the ENCODER memory length (cfg.enc_seq_len) so the
        # cross cache the prefill writes matches the decode-step template
        t["frames"] = jax.ShapeDtypeStruct((B, cfg.enc_seq_len, cfg.d_model),
                                           jnp.bfloat16)
        s["frames"] = P(bt, None, None)
        t["dec_tokens"] = jax.ShapeDtypeStruct((B, S), jnp.int32)
        s["dec_tokens"] = P(bt, None)
    if cfg.frontend == "vision_patches":
        t["image_embeds"] = jax.ShapeDtypeStruct(
            (B, cfg.n_frontend_embeds, cfg.d_model), jnp.bfloat16)
        s["image_embeds"] = P(bt, None, None)
    return t, s


def _serve_bt(plan, shape, mesh):
    return batch_axes(plan) \
        if _batch_sharded(plan, shape.global_batch, mesh) else None


def make_decode_step(cfg, plan, mesh, shape: ShapeConfig):
    prepare_ledger(mesh)
    lay = model_layout(cfg, plan)
    pspecs = model.param_pspecs(cfg, plan)
    t, s = serve_templates(cfg, plan, shape, mesh)
    bt = _serve_bt(plan, shape, mesh)

    def decode(params, cache, tokens, pos):
        return model.forward_decode(params, cache, tokens, pos, cfg, plan, lay)

    fn = _shard_map(decode, mesh,
                    in_specs=(pspecs, s["cache"], s["tokens1"], s["pos"]),
                    out_specs=(P(bt, "model"), s["cache"]))
    return fn, t, s


def make_prefill_step(cfg, plan, mesh, shape: ShapeConfig):
    prepare_ledger(mesh)
    lay = model_layout(cfg, plan)
    pspecs = model.param_pspecs(cfg, plan)
    t, s = serve_templates(cfg, plan, shape, mesh)
    bt = _serve_bt(plan, shape, mesh)

    if cfg.is_encdec:
        def prefill(params, frames, dec_tokens, cache):
            return model.forward_prefill(params, frames, cache, cfg, plan,
                                         lay, extra={"dec_tokens": dec_tokens})
        fn = _shard_map(prefill, mesh,
                        in_specs=(pspecs, s["frames"], s["dec_tokens"],
                                  s["cache"]),
                        out_specs=(P(bt, "model"), s["cache"]))
    elif cfg.frontend == "vision_patches":
        def prefill(params, prompt, image_embeds, cache):
            return model.forward_prefill(params, prompt, cache, cfg, plan,
                                         lay, extra={"image_embeds": image_embeds})
        fn = _shard_map(prefill, mesh,
                        in_specs=(pspecs, s["prompt"], s["image_embeds"],
                                  s["cache"]),
                        out_specs=(P(bt, "model"), s["cache"]))
    else:
        def prefill(params, prompt, cache):
            return model.forward_prefill(params, prompt, cache, cfg, plan, lay)
        fn = _shard_map(prefill, mesh,
                        in_specs=(pspecs, s["prompt"], s["cache"]),
                        out_specs=(P(bt, "model"), s["cache"]))
    return fn, t, s


def zero_cache_for(cfg, plan, mesh, batch, budget):
    """Empty contiguous cache, placed on ``mesh`` with the specs that
    ``serve_templates`` gives the decode/prefill steps."""
    lay = model_layout(cfg, plan)
    tmpl = kvcache.cache_template(
        cfg, plan, lay, batch, budget,
        batch_sharded=_batch_sharded(plan, batch, mesh))
    return kvcache.zero_cache(tmpl, mesh)


# ---------------------------------------------------------------------------
# Paged serving steps (block-table KV + chunked prefill)
# ---------------------------------------------------------------------------
#
# One compiled (decode, prefill-chunk) pair serves every request mix: the
# decode step is shaped by (batch_slots, n_pages, n_max_pages) and the chunk
# step by (chunk, n_pages, n_max_pages) — prompt lengths appear only as data
# (block tables, positions, lengths), never as shapes, so admission never
# recompiles.  Heads keep the model-axis TP sharding; the page pools carry a
# leading replica dim sharded over the data axes (``n_replicas``), so with
# dp>1 each data shard stores only its own replicas' pages.  Block tables
# stay replica-relative: each per-shard function passes every row's local
# replica beside its page ids (``pages["replica"]``), and the layer loop
# carries the stacked pools whole, reading and writing rows by (layer,
# replica, page, offset) or (layer, replica, slab) in place
# (``model._run_paged_stack``), so no step re-shapes, slices or copies a
# pool.  n_replicas == 1 reproduces the dp=1 behavior exactly.
#
# Architecture coverage: attention layers read/write ``kp``/``vp`` page
# pools through block tables; SSM/hybrid layers read/write their
# ``n_slabs`` recurrent-state slab pools by slab id (``slab_ids`` input,
# scratch slab 0 for idle lanes); enc-dec decoders read the encoder
# memory's K/V through a SECOND, read-only block table
# (``cross_block_table``) over the ``ckp``/``cvp`` pools, written once per
# admission by ``make_cross_kv_write_step``.  Each step's input signature
# grows only the pieces the arch needs (``paged_extra_inputs``).


def paged_extra_inputs(cfg) -> tuple:
    """-> (has_ssm, has_cross): which extra inputs (slab_ids /
    cross_block_table) the arch's paged steps take, in that order."""
    prof = kvcache.cache_profile(cfg)
    return "ssm" in prof, "cross_kv" in prof


def _paged_templates(cfg, plan, mesh, n_pages, page_size, n_replicas=1,
                     n_slabs=0):
    assert not plan.seq_shard_kv, "paged cache is exclusive with seq_shard_kv"
    prepare_ledger(mesh)
    lay = model_layout(cfg, plan)
    tmpl = kvcache.paged_cache_template(cfg, plan, lay, n_pages, page_size,
                                        n_replicas, n_slabs)
    return lay, kvcache.abstract_cache(tmpl), kvcache.cache_pspecs(tmpl)


def n_replicas_local(mesh, plan, n_replicas: int) -> int:
    """Replicas resident per data shard.  n_replicas must cover the data
    axes evenly (each shard owns a whole number of replica pools)."""
    nd = n_dp(mesh, plan)
    assert n_replicas % nd == 0, \
        (f"n_replicas={n_replicas} must be a multiple of the mesh's data "
         f"extent {nd}")
    return n_replicas // nd


def make_paged_decode_step(cfg, plan, mesh, batch: int, n_pages: int,
                           page_size: int, n_max_pages: int,
                           n_replicas: int = 1, n_slabs: int = 0):
    """-> (decode_fn(params, cache, tokens (R*B,1), pos (R*B,), block_table
    (R*B, n_max)[, slab_ids (R*B,)][, cross_block_table (R*B, n_cross)])
    -> (logits, cache), templates, specs).  Where
    ``model.counts_expert_picks(cfg)`` the first output is ``(logits,
    picks (R*B,))``: each row's (expert layer, pick) pairs on held experts.

    ``batch`` is the per-replica slot count; the global decode batch covers
    all ``n_replicas`` replicas' slots (rows r*B..r*B+B-1 belong to replica
    r) and is sharded over the data axes alongside the pools, so one
    compiled step drives every replica.  Archs with SSM layers take the
    ``slab_ids`` input (replica-relative, scratch 0 for idle lanes);
    enc-dec archs take the read-only ``cross_block_table``
    (``paged_extra_inputs`` says which apply)."""
    has_ssm, has_cross = paged_extra_inputs(cfg)
    lay, cache_t, cache_s = _paged_templates(cfg, plan, mesh, n_pages,
                                             page_size, n_replicas, n_slabs)
    pspecs = model.param_pspecs(cfg, plan)
    r_loc = n_replicas_local(mesh, plan, n_replicas)
    bt_ax = batch_axes(plan)
    n_cross = kvcache.pages_needed(cfg.enc_seq_len, page_size) \
        if has_cross else 0

    def paged_decode(params, cache, tokens, pos, block_table, *extra):
        # rows stay replica-relative: row i belongs to this shard's local
        # replica i // batch, which indexes the pools beside the page ids
        pages = {"block_table": block_table, "page_size": page_size,
                 "replica": jnp.arange(r_loc * batch, dtype=jnp.int32)
                 // batch}
        extra = list(extra)
        if has_ssm:
            pages["slab_ids"] = extra.pop(0)
        if has_cross:
            pages["cross_block_table"] = extra.pop(0)
        return model.forward_decode(params, cache, tokens, pos, cfg, plan,
                                    lay, pages=pages)

    s = {"cache": cache_s, "tokens1": P(bt_ax, None), "pos": P(bt_ax),
         "block_table": P(bt_ax, None)}
    t = {"cache": cache_t,
         "tokens1": jax.ShapeDtypeStruct((n_replicas * batch, 1), jnp.int32),
         "pos": jax.ShapeDtypeStruct((n_replicas * batch,), jnp.int32),
         "block_table": jax.ShapeDtypeStruct(
             (n_replicas * batch, n_max_pages), jnp.int32)}
    extra_s = []
    if has_ssm:
        s["slab_ids"] = P(bt_ax)
        t["slab_ids"] = jax.ShapeDtypeStruct((n_replicas * batch,), jnp.int32)
        extra_s.append(s["slab_ids"])
    if has_cross:
        s["cross_block_table"] = P(bt_ax, None)
        t["cross_block_table"] = jax.ShapeDtypeStruct(
            (n_replicas * batch, n_cross), jnp.int32)
        extra_s.append(s["cross_block_table"])
    out_s = P(bt_ax, "model")
    if model.counts_expert_picks(cfg):
        out_s = (out_s, P(bt_ax))
    fn = _shard_map(paged_decode, mesh,
                    in_specs=(pspecs, s["cache"], s["tokens1"], s["pos"],
                              s["block_table"], *extra_s),
                    out_specs=(out_s, s["cache"]))
    return fn, t, s


def make_verify_step(cfg, plan, mesh, batch: int, q_len: int, n_pages: int,
                     page_size: int, n_max_pages: int, n_replicas: int = 1):
    """-> (verify_fn(params, cache, tokens (R*B, Q), pos (R*B,), qlen (R*B,),
    block_table (R*B, n_max)) -> (logits (R*B, Q, V), cache), templates,
    specs).

    The speculative-decoding companion of ``make_paged_decode_step``: one
    fused call scores Q = k+1 positions per slot (the last accepted token
    plus k drafted continuations), writing all Q tokens' KV through the
    block table and reading the whole cache once.  ``qlen`` marks the live
    columns per row; padded columns write to the scratch page and their
    logits rows are garbage the engine ignores.  Attention-only archs
    only: SSM recurrences advance strictly one token per step and cross
    archs gate speculation off at the engine."""
    has_ssm, has_cross = paged_extra_inputs(cfg)
    assert not (has_ssm or has_cross), \
        f"verify step requires an attention-only arch, got '{cfg.name}'"
    lay, cache_t, cache_s = _paged_templates(cfg, plan, mesh, n_pages,
                                             page_size, n_replicas, 0)
    pspecs = model.param_pspecs(cfg, plan)
    r_loc = n_replicas_local(mesh, plan, n_replicas)
    bt_ax = batch_axes(plan)

    def verify(params, cache, tokens, pos, qlen, block_table):
        pages = {"block_table": block_table, "page_size": page_size,
                 "replica": jnp.arange(r_loc * batch, dtype=jnp.int32)
                 // batch}
        return model.forward_verify(params, cache, tokens, pos, qlen, cfg,
                                    plan, lay, pages=pages)

    s = {"cache": cache_s, "tokens": P(bt_ax, None), "pos": P(bt_ax),
         "qlen": P(bt_ax), "block_table": P(bt_ax, None)}
    t = {"cache": cache_t,
         "tokens": jax.ShapeDtypeStruct((n_replicas * batch, q_len),
                                        jnp.int32),
         "pos": jax.ShapeDtypeStruct((n_replicas * batch,), jnp.int32),
         "qlen": jax.ShapeDtypeStruct((n_replicas * batch,), jnp.int32),
         "block_table": jax.ShapeDtypeStruct(
             (n_replicas * batch, n_max_pages), jnp.int32)}
    fn = _shard_map(verify, mesh,
                    in_specs=(pspecs, s["cache"], s["tokens"], s["pos"],
                              s["qlen"], s["block_table"]),
                    out_specs=(P(bt_ax, None, "model"), s["cache"]))
    return fn, t, s


def make_prefill_chunk_step(cfg, plan, mesh, chunk: int, n_pages: int,
                            page_size: int, n_max_pages: int,
                            n_replicas: int = 1, n_slabs: int = 0):
    """-> (chunk_fn(params, cache, tokens (R,C), chunk_start (R,), last_idx
    (R,), block_table (R, n_max)[, slab_ids (R,)][, cross_block_table
    (R, n_cross)]) -> (logits (R, V), cache), templates, specs).  Where
    ``model.counts_expert_picks(cfg)`` the first output is ``(logits,
    picks (R,))``: each row's (expert layer, pick) pairs on held experts
    over the chunk's prompt positions.

    Row r advances one prefill chunk for replica r; a replica with nothing
    to prefill rides along pointed at its scratch page (all-SCRATCH_PAGE
    block-table row, zero tokens) and its logits row is ignored.  On a dp
    mesh each shard runs only its own replicas' chunks in parallel.

    SSM layers carry their recurrent state across chunks through the slab
    (``slab_ids``); ``last_idx`` doubles as the recurrence mask — padded
    positions past it leave the state untouched, so the state handed to
    decode is exactly the prompt's.  Enc-dec cross-attention reads the
    admission-time cross pages through ``cross_block_table``."""
    has_ssm, has_cross = paged_extra_inputs(cfg)
    lay, cache_t, cache_s = _paged_templates(cfg, plan, mesh, n_pages,
                                             page_size, n_replicas, n_slabs)
    pspecs = model.param_pspecs(cfg, plan)
    r_loc = n_replicas_local(mesh, plan, n_replicas)
    bt_ax = batch_axes(plan)
    n_cross = kvcache.pages_needed(cfg.enc_seq_len, page_size) \
        if has_cross else 0

    def prefill_chunk(params, cache, tokens, chunk_start, last_idx, block_table,
                  *extra):
        extra = list(extra)
        slab_ids = extra.pop(0) if has_ssm else None
        cross_bt = extra.pop(0) if has_cross else None
        logits = []
        for i in range(r_loc):               # one chunk per local replica
            pages = {"block_table": block_table[i:i + 1],
                     "page_size": page_size,
                     "replica": jnp.full((1,), i, jnp.int32)}
            if has_ssm:
                pages["slab_ids"] = slab_ids[i:i + 1]
                pages["last_idx"] = last_idx[i]
            if has_cross:
                pages["cross_block_table"] = cross_bt[i:i + 1]
            lg, cache = model.forward_prefill_chunk(
                params, cache, tokens[i:i + 1], chunk_start[i],
                last_idx[i], cfg, plan, lay, pages)
            logits.append(lg)
        return jax.tree_util.tree_map(
            lambda *rows: jnp.concatenate(rows, axis=0), *logits), cache

    s = {"cache": cache_s, "tokens": P(bt_ax, None),
         "chunk_start": P(bt_ax), "last_idx": P(bt_ax),
         "block_table": P(bt_ax, None)}
    t = {"cache": cache_t,
         "tokens": jax.ShapeDtypeStruct((n_replicas, chunk), jnp.int32),
         "chunk_start": jax.ShapeDtypeStruct((n_replicas,), jnp.int32),
         "last_idx": jax.ShapeDtypeStruct((n_replicas,), jnp.int32),
         "block_table": jax.ShapeDtypeStruct((n_replicas, n_max_pages),
                                             jnp.int32)}
    extra_s = []
    if has_ssm:
        s["slab_ids"] = P(bt_ax)
        t["slab_ids"] = jax.ShapeDtypeStruct((n_replicas,), jnp.int32)
        extra_s.append(s["slab_ids"])
    if has_cross:
        s["cross_block_table"] = P(bt_ax, None)
        t["cross_block_table"] = jax.ShapeDtypeStruct(
            (n_replicas, n_cross), jnp.int32)
        extra_s.append(s["cross_block_table"])
    out_s = P(bt_ax, "model")
    if model.counts_expert_picks(cfg):
        out_s = (out_s, P(bt_ax))
    fn = _shard_map(prefill_chunk, mesh,
                    in_specs=(pspecs, s["cache"], s["tokens"],
                              s["chunk_start"], s["last_idx"],
                              s["block_table"], *extra_s),
                    out_specs=(out_s, s["cache"]))
    return fn, t, s


def make_page_copy_step(cfg, plan, mesh, n_pages: int, page_size: int,
                        n_replicas: int = 1, n_slabs: int = 0):
    """-> (copy_fn(cache, src (R,), dst (R,)) -> cache, templates, specs).

    Copies one page's K/V across every layer's SELF-KV pool, per replica —
    the mechanism behind copy-on-write divergence: a slot that must append
    into a shared page (radix prefix cache, ``serving.prefix_cache``) first
    duplicates it into a private page, then writes only the copy.  Page ids
    are replica-relative data, so one compiled step serves every (src, dst)
    mix; a replica with no copy this call passes src == dst (identity).
    SSM slab pools (different id space) and cross-KV pools (immutable,
    refcount-shared, never COW'd) pass through untouched."""
    _, cache_t, cache_s = _paged_templates(cfg, plan, mesh, n_pages,
                                           page_size, n_replicas, n_slabs)
    r_loc = n_replicas_local(mesh, plan, n_replicas)
    bt_ax = batch_axes(plan)

    def page_copy(cache, src, dst):
        def leaf(pool):          # (reps, R_loc, n_pages, psz, G, D)
            per = pool.shape[2] // n_pages       # rows a page spans
            size = (pool.shape[0], 1, per) + pool.shape[3:]
            tail = (0,) * (pool.ndim - 3)
            for i in range(r_loc):
                page = jax.lax.dynamic_slice(pool, (0, i, src[i] * per)
                                             + tail, size)
                pool = jax.lax.dynamic_update_slice(
                    pool, page, (0, i, dst[i] * per) + tail)
            return pool
        # only the self-KV pools: slab/cross ids live in other spaces
        return [[{kind: (jax.tree_util.tree_map(leaf, sub)
                         if kind == "kv" else sub)
                  for kind, sub in d.items()} for d in pat]
                for pat in cache]

    s = {"cache": cache_s, "src": P(bt_ax), "dst": P(bt_ax)}
    t = {"cache": cache_t,
         "src": jax.ShapeDtypeStruct((n_replicas,), jnp.int32),
         "dst": jax.ShapeDtypeStruct((n_replicas,), jnp.int32)}
    fn = _shard_map(page_copy, mesh,
                    in_specs=(s["cache"], s["src"], s["dst"]),
                    out_specs=s["cache"])
    return fn, t, s


def make_page_transfer_step(cfg, plan, mesh, n_pages: int, page_size: int,
                            n_lanes: int, n_replicas: int = 1,
                            n_slabs: int = 0):
    """-> (transfer_fn(cache, src_rep, dst_rep, src_pages (n_lanes,),
    dst_pages (n_lanes,)) -> cache, templates, specs).

    First-class inter-replica page movement: gathers up to ``n_lanes``
    pages (payload AND the int8 per-page scale rows — every leaf of the
    self-KV pools rides along byte-identically) from the source replica's
    pool and scatters them into freshly allocated destination pages.  One
    compiled step covers every (src, dst) replica pair: the replica ids
    are scalar *data*, shards that own neither replica route their writes
    to the scratch page, and the gathered pages cross data shards through
    a ledger-tracked psum (identity — zero wire bytes — when source and
    destination live on the same shard, e.g. any 1-shard mesh).  Unused
    lanes pass scratch→scratch.  Host-side refcount ownership moves
    separately and atomically via ``kvcache.handoff_refs``.

    The disaggregated-serving substrate: prefill replicas hand finished
    KV page runs to decode replicas without re-running prefill.  Only the
    self-KV pools transfer — SSM slabs and cross-KV pools are gated off
    by the engine (attention-only models)."""
    prepare_ledger(mesh)
    _, cache_t, cache_s = _paged_templates(cfg, plan, mesh, n_pages,
                                           page_size, n_replicas, n_slabs)
    r_loc = n_replicas_local(mesh, plan, n_replicas)
    sizes = mesh_axis_sizes(mesh)

    def page_transfer(cache, src_rep, dst_rep, src_pages, dst_pages):
        shard = jnp.int32(0)
        for a in plan.dp_axes:
            if sizes.get(a, 1) > 1:
                shard = shard * sizes[a] + jax.lax.axis_index(a)
        base = shard * r_loc
        local_src = src_rep - base
        src_ok = (local_src >= 0) & (local_src < r_loc)
        local_dst = dst_rep - base
        dst_ok = (local_dst >= 0) & (local_dst < r_loc)

        def leaf(pool):          # (reps, R_loc, n_pages * rows a page, ...)
            per = pool.shape[2] // n_pages
            src_r = jnp.clip(local_src, 0, r_loc - 1)
            data = pool[:, src_r, kvcache.page_rows(src_pages, per)]
            data = jnp.where(src_ok, data, jnp.zeros_like(data))
            data = cc.psum(data, tuple(plan.dp_axes), "page_transfer")
            # non-owners write their scratch page
            dst_r = jnp.clip(local_dst, 0, r_loc - 1)
            dst_rows = kvcache.page_rows(jnp.where(dst_ok, dst_pages, 0), per)
            return pool.at[:, dst_r, dst_rows].set(
                jnp.where(dst_ok, data, pool[:, dst_r, dst_rows]))
        # only the self-KV pools: slab/cross ids live in other spaces
        return [[{kind: (jax.tree_util.tree_map(leaf, sub)
                         if kind == "kv" else sub)
                  for kind, sub in d.items()} for d in pat]
                for pat in cache]

    s = {"cache": cache_s, "src_rep": P(), "dst_rep": P(),
         "src_pages": P(None), "dst_pages": P(None)}
    t = {"cache": cache_t,
         "src_rep": jax.ShapeDtypeStruct((), jnp.int32),
         "dst_rep": jax.ShapeDtypeStruct((), jnp.int32),
         "src_pages": jax.ShapeDtypeStruct((n_lanes,), jnp.int32),
         "dst_pages": jax.ShapeDtypeStruct((n_lanes,), jnp.int32)}
    fn = _shard_map(page_transfer, mesh,
                    in_specs=(s["cache"], s["src_rep"], s["dst_rep"],
                              s["src_pages"], s["dst_pages"]),
                    out_specs=s["cache"])
    return fn, t, s


def make_cross_kv_write_step(cfg, plan, mesh, n_pages: int, page_size: int,
                             n_replicas: int = 1, n_slabs: int = 0):
    """-> (write_fn(params, cache, frames (R, S_enc, E), cross_bt
    (R, n_cross)) -> cache, templates, specs).

    The enc-dec admission step: row r runs the ENCODER over replica r's
    frames, projects every cross-attention layer's K/V of the encoder
    memory, and scatters them into the ``ckp``/``cvp`` pools at the pages
    named by its cross block table.  Runs once per admitted request (or
    never, when the frames digest hits the replica's cross-KV cache);
    the written pages are immutable afterwards — decode and chunked
    prefill only read them — so identical-frame requests share them by
    refcount alone.  A replica with nothing to encode rides along with
    zero frames pointed at the scratch page."""
    from repro.core.blocks import _kv_q
    assert paged_extra_inputs(cfg)[1], \
        f"{cfg.name} has no cross-attention layers"
    lay, cache_t, cache_s = _paged_templates(cfg, plan, mesh, n_pages,
                                             page_size, n_replicas, n_slabs)
    pspecs = model.param_pspecs(cfg, plan)
    r_loc = n_replicas_local(mesh, plan, n_replicas)
    bt_ax = batch_axes(plan)
    S_enc = cfg.enc_seq_len
    n_cross = kvcache.pages_needed(S_enc, page_size)

    def scatter(pool, kv1, bt_row, i):
        """pool: (reps, R_loc, n_pages, psz, G, D); kv1: (reps, G, S_enc,
        D) -> pool with position s written at replica i's page
        bt_row[s // psz], offset s % psz."""
        pids = jnp.take(bt_row, jnp.arange(S_enc) // page_size)
        offs = jnp.arange(S_enc) % page_size
        val = _kv_q(kv1, pool.dtype).transpose(0, 2, 1, 3)  # (reps,S_enc,G,D)
        return pool.at[:, i, pids, offs].set(val)

    def scatter_q(pool, sc, kv1, bt_row, i):
        """int8 pools: per-token-row quantization, scale scattered into the
        flat (reps, R_loc, n_pages*psz) side tensor atomically with the
        payload (same row scheme as ``blocks._row_quant``)."""
        pids = jnp.take(bt_row, jnp.arange(S_enc) // page_size)
        offs = jnp.arange(S_enc) % page_size
        kf = kv1.astype(jnp.float32)
        amax = jnp.max(jnp.abs(kf), axis=(1, 3))            # (reps, S_enc)
        inv = jnp.where(amax > 0, 127.0 / jnp.maximum(amax, 1e-30), 0.0)
        q = jnp.clip(jnp.round(kf * inv[:, None, :, None]),
                     -127, 127).astype(jnp.int8)
        val = q.transpose(0, 2, 1, 3)                       # (reps,S_enc,G,D)
        return (pool.at[:, i, pids, offs].set(val),
                sc.at[:, i, pids * page_size + offs].set(
                    amax * (1.0 / 127.0)))

    def cross_kv_write(params, cache, frames, cross_bt):
        cache = [list(pat) for pat in cache]
        for i in range(r_loc):
            enc = model.encode(params,
                               frames[i:i + 1].astype(jnp.dtype(cfg.dtype)),
                               cfg, plan, lay)
            kvs = model.forward_cross_kv(params, enc, cfg, plan, lay)
            for gi, per_pat in enumerate(kvs):
                for pi, kv in enumerate(per_pat):
                    if kv is None:
                        continue
                    cr = cache[gi][pi]["cross"]
                    if "cksp" in cr:
                        ckp, cksp = scatter_q(cr["ckp"], cr["cksp"],
                                              kv["k"][:, 0], cross_bt[i], i)
                        cvp, cvsp = scatter_q(cr["cvp"], cr["cvsp"],
                                              kv["v"][:, 0], cross_bt[i], i)
                        cr = {"ckp": ckp, "cvp": cvp,
                              "cksp": cksp, "cvsp": cvsp}
                    else:
                        cr = {"ckp": scatter(cr["ckp"], kv["k"][:, 0],
                                             cross_bt[i], i),
                              "cvp": scatter(cr["cvp"], kv["v"][:, 0],
                                             cross_bt[i], i)}
                    cache[gi][pi] = dict(cache[gi][pi], cross=cr)
        return cache

    s = {"cache": cache_s, "frames": P(bt_ax, None, None),
         "cross_bt": P(bt_ax, None)}
    t = {"cache": cache_t,
         "frames": jax.ShapeDtypeStruct((n_replicas, S_enc, cfg.d_model),
                                        jnp.dtype(cfg.dtype)),
         "cross_bt": jax.ShapeDtypeStruct((n_replicas, n_cross), jnp.int32)}
    fn = _shard_map(cross_kv_write, mesh,
                    in_specs=(pspecs, s["cache"], s["frames"], s["cross_bt"]),
                    out_specs=s["cache"])
    return fn, t, s


def zero_paged_cache_for(cfg, plan, mesh, n_pages, page_size,
                         n_replicas: int = 1, n_slabs: int = 0):
    lay = model_layout(cfg, plan)
    tmpl = kvcache.paged_cache_template(cfg, plan, lay, n_pages, page_size,
                                        n_replicas, n_slabs)
    return kvcache.zero_paged_cache(tmpl, mesh)


@functools.partial(jax.jit, donate_argnums=(0,))
def zero_slab(pool, replica, slab):
    """-> an SSM state pool ``(reps, R, n_slabs, ...)`` with replica
    ``replica``'s slab ``slab`` zeroed: the paged engine's reset of a
    fresh request's recurrent state, as one compiled module named
    ``jit_zero_slab`` on a trace.  The pool is donated and zeroed in
    place; a new pool would hold a second copy of every slab."""
    return pool.at[:, replica, slab].set(0)


@functools.partial(jax.jit, static_argnums=(1,))
def greedy_ids(logits, vocab_size: int):
    """-> ``(N,)`` int32, the argmax of each row of ``logits`` ``(N,
    V_pad)`` over its first ``vocab_size`` columns, ties to the first
    index (as ``np.argmax``): the greedy tokens of a step, picked on the
    device as one compiled module named ``jit_greedy_ids``.  Padding
    columns are masked rather than sliced off, so logits whose vocabulary
    is sharded over ``model`` stay sharded and the compiler reduces the
    argmax across the shards."""
    col = jnp.arange(logits.shape[-1])
    return jnp.argmax(jnp.where(col < vocab_size, logits, -jnp.inf),
                      axis=-1).astype(jnp.int32)


_STEP_SET_MEMO: dict = {}


def paged_step_set(cfg, plan, mesh, batch: int, n_pages: int, page_size: int,
                   n_max_pages: int, prefill_chunk: int, n_replicas: int = 1,
                   n_slabs: int = 0, speculative: int = 0) -> dict:
    """-> memoized dict of the jitted paged-engine steps for one shape:
    ``{"prefill", "decode", "copy", "cross_write", "verify", "transfer"}``
    (entries the arch/shape doesn't need are None).

    jax.jit caches compilations per *function object*, so an engine that
    rebuilds its steps on every membership change (``scale_to`` /
    ``kill_replica``) would recompile from scratch each time it revisits a
    replica count.  Memoizing the jitted closures on the step shape makes
    repeated reconfiguration — and fault-injection suites that build many
    engines over the same config — pay compilation once per distinct
    (cfg, mesh, plan, batch, pool, n_replicas) tuple.  The memo holds
    cfg/mesh strongly so their ids cannot be recycled under a live key.

    Donation matches the engine's call conventions: every step that
    threads the cache donates it (arg 1 after params, or arg 0 for the
    param-less copy/transfer steps)."""
    key = (id(cfg), id(mesh), plan, batch, n_pages, page_size, n_max_pages,
           prefill_chunk, n_replicas, n_slabs, speculative)
    hit = _STEP_SET_MEMO.get(key)
    if hit is not None:
        return hit[2]
    has_ssm, has_cross = paged_extra_inputs(cfg)
    prof = kvcache.cache_profile(cfg)
    slabs = n_slabs if has_ssm else 0
    dec, _, _ = make_paged_decode_step(cfg, plan, mesh, batch, n_pages,
                                       page_size, n_max_pages,
                                       n_replicas=n_replicas, n_slabs=slabs)
    chunk_fn, _, _ = make_prefill_chunk_step(cfg, plan, mesh, prefill_chunk,
                                             n_pages, page_size, n_max_pages,
                                             n_replicas=n_replicas,
                                             n_slabs=slabs)
    out = {"decode": jax.jit(dec, donate_argnums=(1,)),
           "prefill": jax.jit(chunk_fn, donate_argnums=(1,)),
           "copy": None, "cross_write": None, "verify": None,
           "transfer": None}
    if "kv" in prof:
        cp, _, _ = make_page_copy_step(cfg, plan, mesh, n_pages, page_size,
                                       n_replicas=n_replicas, n_slabs=slabs)
        out["copy"] = jax.jit(cp, donate_argnums=(0,))
    if has_cross:
        cw, _, _ = make_cross_kv_write_step(cfg, plan, mesh, n_pages,
                                            page_size, n_replicas=n_replicas,
                                            n_slabs=slabs)
        out["cross_write"] = jax.jit(cw, donate_argnums=(1,))
    if speculative > 0:
        vf, _, _ = make_verify_step(cfg, plan, mesh, batch, speculative + 1,
                                    n_pages, page_size, n_max_pages,
                                    n_replicas=n_replicas)
        out["verify"] = jax.jit(vf, donate_argnums=(1,))
    if n_replicas > 1 and not has_ssm and not has_cross:
        tf, _, _ = make_page_transfer_step(cfg, plan, mesh, n_pages,
                                           page_size, n_max_pages,
                                           n_replicas=n_replicas)
        out["transfer"] = jax.jit(tf, donate_argnums=(0,))
    # hold cfg/mesh strongly so their ids cannot be recycled under the key
    _STEP_SET_MEMO[key] = (cfg, mesh, out)
    return out
