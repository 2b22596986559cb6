"""Per-shard transformer layer forward — the paper's §IV contract in code.

Every layer issues **exactly one psum per weight-partitioned sublayer**:
one after the mixer (attention / SSD / hybrid fusion), one after the FFN
(enc-dec adds one for cross-attention).  All head/F/expert compute is local.
The residual is added around the reduced value — the paper's "skip folded
into the all-reduce".  All collectives go through the CommLedger so the
contract is audited by tests and the roofline.

All functions here run INSIDE shard_map: tp-sharded params carry a leading
local axis of size 1 (``_lo`` strips it), replicated params arrive whole.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import FFN_MOE, FFN_NONE, MIX_ATTN, MIX_NONE, \
    MIX_SSM
from repro.core import collectives as cc
from repro.core import ssm as ssd
from repro.core.attention import decode_attention, flash_attention, \
    gather_kv, paged_decode_attention, paged_verify_attention
from repro.core.layers import activation, apply_norm, apply_rope, rmsnorm, \
    rmsnorm_from_sumsq
from repro.core.moe import moe_ffn


W8_SCALE = 64.0        # per-tensor int8 weight scale (deployment experiments;
                       # production would carry per-channel scales)
KVQ = {"scale": 16.0}  # fixed-point int8 KV scale (set from plan at trace)


def _lo(w):
    x = w[0]
    if x.dtype == jnp.int8:
        return (x.astype(jnp.float32) * (1.0 / W8_SCALE)).astype(jnp.bfloat16)
    return x


def _kv_q(x, dtype):
    """Quantize k/v for the cache (int8 fixed-point or plain cast)."""
    if jnp.dtype(dtype) == jnp.int8:
        return jnp.clip(jnp.round(x.astype(jnp.float32) * KVQ["scale"]),
                        -127, 127).astype(jnp.int8)
    return x.astype(dtype)


def _kv_dq(x, compute_dtype):
    if x.dtype == jnp.int8:
        return (x.astype(jnp.float32) * (1.0 / KVQ["scale"])
                ).astype(compute_dtype)
    return x.astype(compute_dtype)


def _row_quant(x):
    """Per-token-row int8 quantization for the paged pools.

    x: (..., G, D) — one token row per leading index.  Each row gets its
    own scale ``amax / 127`` over its (G, D) values, so the stored bytes
    are a pure function of the row's value: write order, speculation
    rollbacks and preemption/resume chunking cannot change them (the
    schedule-invariance the identity gates rely on).  A zero row gets
    scale 0 and dequantizes to exact zeros.  -> (int8 like x, scale
    (...,) float32)."""
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=(-2, -1))
    inv = jnp.where(amax > 0, 127.0 / jnp.maximum(amax, 1e-30), 0.0)
    q = jnp.clip(jnp.round(xf * inv[..., None, None]),
                 -127, 127).astype(jnp.int8)
    return q, amax * (1.0 / 127.0)


def shard_index(axis="model"):
    return jax.lax.axis_index(axis) if cc.axis_size((axis,)) > 1 else 0


def tp_index(plan):
    """This device's tensor-parallel shard index (0 when tp == 1)."""
    return shard_index(plan.tp_axis) if plan.tp > 1 else 0


def dp_linear_index(dp_axes):
    idx = 0
    for a in dp_axes:
        n = cc.axis_size((a,))
        idx = idx * n + (jax.lax.axis_index(a) if n > 1 else 0)
    return idx


# ---------------------------------------------------------------------------
# Attention mixer
# ---------------------------------------------------------------------------

def _project_qkv(xn, pa, cfg, lay):
    q = jnp.einsum("bse,ehd->bshd", xn, _lo(pa["wq"]))
    k = jnp.einsum("bse,ehd->bshd", xn, _lo(pa["wk"]))
    v = jnp.einsum("bse,ehd->bshd", xn, _lo(pa["wv"]))
    if cfg.qk_norm:
        q = rmsnorm(q, pa["q_norm"], cfg.norm_eps)
        k = rmsnorm(k, pa["k_norm"], cfg.norm_eps)
    return q, k, v


def _rope_qk(q, k, positions, cfg):
    if cfg.rope_theta > 0:
        q = _rope_heads(q, positions, cfg)
        k = _rope_heads(k, positions, cfg)
    return q, k


def _rope_heads(x, positions, cfg):
    # x: (B, S, H, D); positions: (B, S)
    xt = x.swapaxes(1, 2)                           # (B, H, S, D)
    xt = apply_rope(xt, positions[:, None, :], cfg.rope_theta)
    return xt.swapaxes(1, 2)


def _group_q(q, lay):
    """(B,S,hq_loc,D) -> (B, G, R, S, D)"""
    B, S, _, D = q.shape
    hl = lay.attn
    q = q.reshape(B, S, hl.n_kv_loc, hl.r, D)
    return q.transpose(0, 2, 3, 1, 4)


def _ungroup(o, lay):
    """(B,G,R,S,D) -> (B,S,hq_loc*D)"""
    B, G, R, S, D = o.shape
    return o.transpose(0, 3, 1, 2, 4).reshape(B, S, G * R * D)


def attn_mixer(xn, pa, cfg, plan, lay, spec, mode, kv_cache, positions, pos,
               pages=None):
    """-> (partial_out (B,S,E), new_kv_cache)."""
    B, S, E = xn.shape
    hl = lay.attn
    d = cfg.head_dim_
    window = cfg.window_for(spec)
    q, k, v = _project_qkv(xn, pa, cfg, lay)
    q, k = _rope_qk(q, k, positions, cfg)
    qg = _group_q(q, lay)                            # (B,G,R,S,D)
    kg = k.swapaxes(1, 2)                            # (B,G,S,D)
    vg = v.swapaxes(1, 2)
    new_cache = kv_cache

    if kv_cache is not None and "kp" in kv_cache:    # paged path
        out, new_cache = _paged_attn(qg, kg, vg, kv_cache, pages, mode,
                                     positions, pos, window, cfg)
    elif mode == "decode":
        new_cache = _kv_write(kv_cache, kg, vg, pos, plan)
        out = decode_attention(
            qg[:, :, :, 0], _kv_dq(new_cache["k"], qg.dtype),
            _kv_dq(new_cache["v"], qg.dtype), new_cache["pos"], pos,
            window=window, scale=cfg.attn_scale,
            seq_axes=tuple(plan.dp_axes) if plan.seq_shard_kv else ())
        out = out[:, :, :, None, :]                  # (B,G,R,1,D)
    else:
        from repro.core.attention import flash_attention_split
        if plan.attn_scheme == "split" and cfg.causal and window == 0:
            out = flash_attention_split(qg, kg, vg, scale=cfg.attn_scale)
        else:
            out = flash_attention(qg, kg, vg, causal=cfg.causal,
                                  window=window, scale=cfg.attn_scale)
        if mode == "prefill" and kv_cache is not None:
            new_cache = _kv_fill(kv_cache, kg, vg, positions, plan)

    o = _ungroup(out, lay)                           # (B,S,hq_loc*D)
    return jnp.einsum("bsx,xe->bse", o,
                      _lo(pa["wo"]).reshape(hl.hq_loc * d, E)), new_cache


def cross_attn_mixer(xn, pa, cfg, plan, lay, mode, cross_cache, enc_memory,
                     pages=None):
    """Cross-attention: q from x, kv from encoder memory (or cross cache).

    Paged path (``cross_cache`` holds ``ckp``/``cvp`` pools): K/V were
    written once at admission by ``steps.make_cross_kv_write_step`` and are
    READ-ONLY here — both decode and chunked prefill gather them through
    the slot's cross block table (``pages["cross_block_table"]``) and slice
    to the static encoder length, so shared cross pages are never written.
    """
    B, S, E = xn.shape
    hl = lay.attn
    d = cfg.head_dim_
    q = jnp.einsum("bse,ehd->bshd", xn, _lo(pa["wq"]))
    if cfg.qk_norm:
        q = rmsnorm(q, pa["q_norm"], cfg.norm_eps)
    qg = _group_q(q, lay)
    if cross_cache is not None and "ckp" in cross_cache:   # paged, read-only
        S_enc = cfg.enc_seq_len
        kg, vg = gather_kv(cross_cache["ckp"], cross_cache["cvp"],
                           pages["cross_block_table"], qg.dtype,
                           at=(pages["layer"], pages["replica"]),
                           k_scale=cross_cache.get("cksp"),
                           v_scale=cross_cache.get("cvsp"))
        kg, vg = kg[:, :, :S_enc], vg[:, :, :S_enc]
        if mode == "decode":
            out = decode_attention(
                qg[:, :, :, 0], kg, vg,
                jnp.broadcast_to(jnp.arange(S_enc), (B, S_enc)),
                jnp.full((B,), S_enc, jnp.int32), window=0,
                scale=cfg.attn_scale)
            out = out[:, :, :, None, :]
        else:
            out = flash_attention(qg, kg, vg, causal=False, window=0,
                                  scale=cfg.attn_scale)
        o = _ungroup(out, lay)
        return jnp.einsum("bsx,xe->bse", o,
                          _lo(pa["wo"]).reshape(hl.hq_loc * d, E)), None
    if mode == "decode":
        kg = cross_cache["k"].astype(qg.dtype)
        vg = cross_cache["v"].astype(qg.dtype)
        S_enc = kg.shape[2]
        out = decode_attention(
            qg[:, :, :, 0], kg, vg,
            jnp.broadcast_to(jnp.arange(S_enc), (B, S_enc)),
            jnp.full((B,), S_enc, jnp.int32), window=0, scale=cfg.attn_scale)
        out = out[:, :, :, None, :]
    else:
        k = jnp.einsum("bse,ehd->bshd", enc_memory, _lo(pa["wk"]))
        v = jnp.einsum("bse,ehd->bshd", enc_memory, _lo(pa["wv"]))
        if cfg.qk_norm:
            k = rmsnorm(k, pa["k_norm"], cfg.norm_eps)
        kg, vg = k.swapaxes(1, 2), v.swapaxes(1, 2)
        out = flash_attention(qg, kg, vg, causal=False, window=0,
                              scale=cfg.attn_scale)
        if mode == "prefill" and cross_cache is not None:
            cross_cache = {"k": kg.astype(cross_cache["k"].dtype),
                           "v": vg.astype(cross_cache["v"].dtype)}
    o = _ungroup(out, lay)
    return jnp.einsum("bsx,xe->bse", o,
                      _lo(pa["wo"]).reshape(hl.hq_loc * d, E)), cross_cache


def _kv_write(kv, kg, vg, pos, plan):
    """Decode-step cache write.  kg/vg: (B, G, 1, D); pos: (B,)."""
    B, G, W, D = kv["k"].shape
    if plan.seq_shard_kv:
        W_glob = W * cc.axis_size(plan.dp_axes)
        slot = pos % W_glob
        me = dp_linear_index(plan.dp_axes)
        owner = slot // W
        local_slot = jnp.clip(slot - owner * W, 0, W - 1)
        own = (owner == me)
        bidx = jnp.arange(B)
        k_new = kv["k"].at[bidx, :, local_slot].set(
            jnp.where(own[:, None, None], _kv_q(kg[:, :, 0], kv["k"].dtype),
                      kv["k"][bidx, :, local_slot]))
        v_new = kv["v"].at[bidx, :, local_slot].set(
            jnp.where(own[:, None, None], _kv_q(vg[:, :, 0], kv["v"].dtype),
                      kv["v"][bidx, :, local_slot]))
        p_new = kv["pos"].at[bidx, local_slot].set(
            jnp.where(own, pos, kv["pos"][bidx, local_slot]))
        return {"k": k_new, "v": v_new, "pos": p_new}
    slot = pos % W
    bidx = jnp.arange(B)
    return {
        "k": kv["k"].at[bidx, :, slot].set(_kv_q(kg[:, :, 0], kv["k"].dtype)),
        "v": kv["v"].at[bidx, :, slot].set(_kv_q(vg[:, :, 0], kv["v"].dtype)),
        "pos": kv["pos"].at[bidx, slot].set(pos),
    }


def _paged_attn(qg, kg, vg, kv, pages, mode, positions, pos, window, cfg):
    """Paged-cache attention (decode token, verify block or prefill chunk).

    kv: {"kp","vp"[,"ksp","vsp"]} stacked page pools (reps, R, n_pages,
    psz, G, D), the layer loop's carried state; pages: {"block_table",
    "page_size", "layer", "replica"}.  Token t of row b lives at page
    block_table[b, t // psz], offset t % psz, of layer ``pages["layer"]``
    and replica ``pages["replica"][b]``; the gathered stream therefore
    holds absolute position s at slot s and validity reduces to s <=
    cur_pos (decode) / causal masking (chunk).
    Garbage between a prompt's end and its chunk boundary is never read:
    every later position is decode-written before it first becomes visible.
    """
    bt, at = pages["block_table"], (pages["layer"], pages["replica"])
    # decode writes one token per row; verify writes all Q tokens of the
    # block (token i at pos + i: token 0 is the slot's last accepted token,
    # the rest are drafts; padded/overflow rows carry position -1 and land
    # on the scratch page); a prefill chunk writes the chunk
    new = _page_write(kv, kg, vg, pos[:, None] if mode == "decode"
                      else positions, bt, pages["page_size"], at)
    read = dict(at=at, k_scale=new.get("ksp"), v_scale=new.get("vsp"))
    if mode == "decode":
        out = paged_decode_attention(qg[:, :, :, 0], new["kp"], new["vp"], bt,
                                     pos, window=window,
                                     scale=cfg.attn_scale, **read)
        return out[:, :, :, None, :], new
    if mode == "verify":
        # score every position against the gathered stream in one pass;
        # acceptance and rollback are host-side pos bookkeeping (rejected
        # KV is masked by validity until the next step overwrites it)
        out = paged_verify_attention(qg, new["kp"], new["vp"], bt, pos,
                                     window=window, scale=cfg.attn_scale,
                                     **read)
        return out, new
    # prefill chunk: attend to the gathered prefix, the chunk included
    k_all, v_all = gather_kv(new["kp"], new["vp"], bt, qg.dtype, **read)
    out = flash_attention(qg, k_all, v_all, causal=True, window=window,
                          scale=cfg.attn_scale, q_offset=positions[0, 0])
    return out, new


def _page_write(kv, kg, vg, positions, bt, psz, at):
    """Scatter new K/V rows into the stacked page pools, in place.
    kg/vg: (B, G, C, D); positions: (B, C) absolute token positions (C = 1
    for decode); at: (layer, replica (B,)).  Each token is one (G, D) row,
    written at (layer, replica, page, offset).  Negative positions (padded
    verify queries) route to the scratch page (page 0), whose contents are
    never read by a live slot.

    Quantized pools (``ksp``/``vsp`` present): each token row is quantized
    independently with its own per-row scale (``_row_quant``), and the
    scale is scattered atomically with the payload into the flat per-(page,
    slot) scale tensor."""
    B, G, C, D = kg.shape
    safe = jnp.maximum(positions, 0)
    pid = jnp.take_along_axis(bt, safe // psz, axis=1)         # (B, C)
    pid = jnp.where(positions >= 0, pid, 0)
    layer, rep = at
    rep = jnp.broadcast_to(rep[:, None], (B, C))
    at = (layer, rep, pid, safe % psz)                         # each (B, C)
    if "ksp" in kv:
        kq, ks = _row_quant(kg.transpose(0, 2, 1, 3))          # (B,C,G,D)
        vq, vs = _row_quant(vg.transpose(0, 2, 1, 3))
        at_s = (layer, rep, pid * psz + safe % psz)            # flat scales
        return {"kp": kv["kp"].at[at].set(kq), "vp": kv["vp"].at[at].set(vq),
                "ksp": kv["ksp"].at[at_s].set(ks),
                "vsp": kv["vsp"].at[at_s].set(vs)}
    kq = _kv_q(kg, kv["kp"].dtype).transpose(0, 2, 1, 3)       # (B,C,G,D)
    vq = _kv_q(vg, kv["vp"].dtype).transpose(0, 2, 1, 3)
    return {"kp": kv["kp"].at[at].set(kq), "vp": kv["vp"].at[at].set(vq)}


def _kv_fill(kv, kg, vg, positions, plan):
    """Prefill cache write: keep the last W tokens at ring slots."""
    B, G, W, D = kv["k"].shape
    S = kg.shape[2]
    if plan.seq_shard_kv:
        # each data shard stores its contiguous slice [me*W, (me+1)*W)
        me = dp_linear_index(plan.dp_axes)
        start = me * W
        take = jnp.clip(jnp.arange(W) + start, 0, S - 1)
        valid = (jnp.arange(W) + start) < S
        k_sl = jnp.take(kg, take, axis=2)
        v_sl = jnp.take(vg, take, axis=2)
        p_sl = jnp.where(valid[None, :],
                         jnp.take(positions, take, axis=1), -1)
        return {"k": _kv_q(k_sl, kv["k"].dtype),
                "v": _kv_q(v_sl, kv["v"].dtype), "pos": p_sl}
    n = min(W, S)
    k_tail, v_tail = kg[:, :, S - n:], vg[:, :, S - n:]
    p_tail = positions[:, S - n:]
    slots = p_tail[0] % W                            # same for all batch rows
    k_new = kv["k"].at[:, :, slots].set(_kv_q(k_tail, kv["k"].dtype))
    v_new = kv["v"].at[:, :, slots].set(_kv_q(v_tail, kv["v"].dtype))
    p_new = kv["pos"].at[:, slots].set(p_tail)
    return {"k": k_new, "v": v_new, "pos": p_new}


# ---------------------------------------------------------------------------
# SSD mixer (mamba2 / hymba SSM heads)
# ---------------------------------------------------------------------------

def _cp_halo(x, plan, K):
    """Receive the previous CP shard's last K-1 rows (conv halo).  The first
    shard gets zeros (ppermute leaves unsourced destinations zero), matching
    causal-conv zero padding at sequence start."""
    axis = plan.cp_axes[0]
    n = cc.axis_size(plan.cp_axes)
    tail = x[:, -(K - 1):]
    return cc.ppermute(tail, axis, [(i, i + 1) for i in range(n - 1)],
                       "block/cp_halo")


def _cp_state_prefix(C_loc, D_loc, plan):
    """Incoming SSD state for this CP shard.

    Gather every shard's (total_decay D_i, state contribution C_i), then
    evaluate the prefix recurrence S_j = S_{j-1} * D_{j-1} + C_{j-1} locally
    (identical on all shards; each selects its own entry).  Payload is tiny
    (states, not activations) — this is what makes SSM context parallelism
    collective-cheap (§Perf hillclimb 3).  Returns (S_in, S_global)."""
    axis = plan.cp_axes[0]
    n = cc.axis_size(plan.cp_axes)
    gdt = jnp.dtype(plan.cp_state_dtype)
    Cg = cc.all_gather(C_loc.astype(gdt)[None], axis,
                       "block/cp_state").astype(jnp.float32)     # (n,B,H,P,N)
    Dg = cc.all_gather(D_loc.astype(gdt)[None], axis,
                       "block/cp_decay").astype(jnp.float32)     # (n,B,H)
    running = jnp.zeros_like(C_loc)
    prefixes = []
    for i in range(n):
        prefixes.append(running)
        running = running * Dg[i][..., None, None] + Cg[i]
    me = dp_linear_index(plan.cp_axes)
    return jnp.take(jnp.stack(prefixes), me, axis=0), running


def ssm_mixer(xn, ps, cfg, plan, lay, mode, ssm_cache, chunk_last_idx=None):
    """-> (partial_out (B,S,E), new_cache).  Heads sharded on model axis.

    ``chunk_last_idx`` enables the *chunked-prefill-with-carried-state*
    path (paged serving): the conv tails and SSD state in ``ssm_cache``
    are the running state after the previous chunk, and positions past
    ``chunk_last_idx`` (zero-padding beyond the prompt's end) must not
    touch the recurrence — their dt is zeroed (decay 1, contribution 0)
    and the conv tail is sliced at the last valid row, so the state handed
    to the next chunk/decode step is exact."""
    B, S, E = xn.shape
    H = lay.ssm.hq_loc
    Pd = cfg.ssm_head_dim
    chunked = chunk_last_idx is not None
    cp = bool(plan.cp_axes) and mode != "decode" and not chunked and \
        cc.axis_size(plan.cp_axes) > 1
    G = cfg.ssm_groups
    bx = _lo(ps["conv_x_bias"]) if cfg.ssm_conv_bias else None
    bB, bC = ps.get("conv_B_bias"), ps.get("conv_C_bias")
    z = jnp.einsum("bse,ehp->bshp", xn, _lo(ps["in_z"]))         # (B,S,H,P)
    xi = jnp.einsum("bse,ehp->bshp", xn, _lo(ps["in_x"]))
    dt_raw = jnp.einsum("bse,eh->bsh", xn, _lo(ps["in_dt"]))
    Bm = jnp.einsum("bse,en->bsn", xn, ps["in_B"])   # replicated, (B,S,G*N)
    Cm = jnp.einsum("bse,en->bsn", xn, ps["in_C"])

    xi_f = xi.reshape(B, S, H * Pd)
    K = cfg.ssm_conv
    if mode == "decode":
        xi_f, cs_x = ssd.causal_conv(xi_f, _lo(ps["conv_x"]).reshape(H * Pd, -1),
                                     ssm_cache["conv_x"], bias=bx)
        Bm, cs_B = ssd.causal_conv(Bm, ps["conv_B"], ssm_cache["conv_B"],
                                   bias=bB)
        Cm, cs_C = ssd.causal_conv(Cm, ps["conv_C"], ssm_cache["conv_C"],
                                   bias=bC)
    elif chunked:
        xi_f, cs_x = ssd.causal_conv(xi_f, _lo(ps["conv_x"]).reshape(H * Pd, -1),
                                     ssm_cache["conv_x"],
                                     tail_idx=chunk_last_idx, bias=bx)
        Bm, cs_B = ssd.causal_conv(Bm, ps["conv_B"], ssm_cache["conv_B"],
                                   tail_idx=chunk_last_idx, bias=bB)
        Cm, cs_C = ssd.causal_conv(Cm, ps["conv_C"], ssm_cache["conv_C"],
                                   tail_idx=chunk_last_idx, bias=bC)
    elif cp:
        # conv halo: previous shard's last K-1 pre-conv rows
        xi_f, cs_x = ssd.causal_conv(xi_f, _lo(ps["conv_x"]).reshape(H * Pd, -1),
                                     _cp_halo(xi_f, plan, K), bias=bx)
        Bm, cs_B = ssd.causal_conv(Bm, ps["conv_B"], _cp_halo(Bm, plan, K),
                                   bias=bB)
        Cm, cs_C = ssd.causal_conv(Cm, ps["conv_C"], _cp_halo(Cm, plan, K),
                                   bias=bC)
    else:
        xi_f, cs_x = ssd.causal_conv(xi_f, _lo(ps["conv_x"]).reshape(H * Pd, -1),
                                     bias=bx)
        Bm, cs_B = ssd.causal_conv(Bm, ps["conv_B"], bias=bB)
        Cm, cs_C = ssd.causal_conv(Cm, ps["conv_C"], bias=bC)
    xi = jax.nn.silu(xi_f).reshape(B, S, H, Pd)
    Bm, Cm = jax.nn.silu(Bm), jax.nn.silu(Cm)
    if G > 1:                        # each local head's group's B and C
        Bm, Cm = _head_groups(Bm, cfg, plan, lay), \
            _head_groups(Cm, cfg, plan, lay)
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) +
                         _lo(ps["dt_bias"]).astype(jnp.float32))
    A = -jnp.exp(_lo(ps["A_log"]).astype(jnp.float32))
    D = _lo(ps["D"])

    if mode == "decode":
        y, state = ssd.ssd_decode_step(xi[:, 0], dt[:, 0], Bm[:, 0], Cm[:, 0],
                                       A, D, ssm_cache["state"])
        y = y[:, None]                                           # (B,1,H,P)
        new_cache = {"state": state, "conv_x": cs_x, "conv_B": cs_B,
                     "conv_C": cs_C}
    elif chunked:
        # padding past the prompt must not advance the recurrence: dt = 0
        # makes a padded position's decay exp(0) = 1 and contribution 0
        dt = jnp.where(jnp.arange(S)[None, :, None] <= chunk_last_idx,
                       dt, 0.0)
        y, state = ssd.ssd_chunked(xi, dt, Bm, Cm, A, D, cfg.ssm_chunk,
                                   state0=ssm_cache["state"])
        new_cache = {"state": state, "conv_x": cs_x, "conv_B": cs_B,
                     "conv_C": cs_C}
    elif cp:
        y0, C_loc, cum_decay, D_loc = ssd.ssd_chunked(
            xi, dt, Bm, Cm, A, D, cfg.ssm_chunk, return_extras=True)
        S_in, S_glob = _cp_state_prefix(C_loc, D_loc, plan)
        # fold the incoming state in (linear correction; exact)
        y_corr = jnp.einsum("bshn,bhpn->bshp" if G > 1 else
                            "bsn,bhpn->bshp", Cm.astype(jnp.float32),
                            S_in) * cum_decay[..., None]
        y = y0 + y_corr.astype(y0.dtype)
        new_cache = None
        if mode == "prefill" and ssm_cache is not None:
            n_cp = cc.axis_size(plan.cp_axes)
            me = dp_linear_index(plan.cp_axes)
            last = (me == n_cp - 1)

            def bcast(t):
                z_ = jnp.where(last, t, jnp.zeros_like(t))
                return cc.psum(z_, plan.cp_axes, "block/cp_tail")
            new_cache = {"state": S_glob, "conv_x": bcast(cs_x),
                         "conv_B": bcast(cs_B), "conv_C": bcast(cs_C)}
    else:
        y, state = ssd.ssd_chunked(xi, dt, Bm, Cm, A, D, cfg.ssm_chunk)
        new_cache = None
        if mode == "prefill" and ssm_cache is not None:
            new_cache = {"state": state, "conv_x": cs_x, "conv_B": cs_B,
                         "conv_C": cs_C}

    g = (y * jax.nn.silu(z.astype(jnp.float32))).reshape(B, S, H * Pd)
    if G > 1:
        # gated RMSNorm over each group's channels; a shard holds whole
        # groups (``_head_groups``), so no sum crosses shards
        gg = g.reshape(B, S, -1, cfg.ssm_inner // G)
        gg = rmsnorm_from_sumsq(
            gg, jnp.sum(jnp.square(gg), axis=-1, keepdims=True),
            cfg.ssm_inner // G, _lo(ps["norm_scale"]).reshape(gg.shape[2:]),
            cfg.norm_eps)
        g = gg.reshape(B, S, H * Pd)
    else:
        # gated RMSNorm over the FULL d_inner (cross-shard sum of squares:
        # one tiny psum — O(B*S) bytes, counted by the ledger)
        sumsq = jnp.sum(jnp.square(g).astype(jnp.float32), axis=-1,
                        keepdims=True)
        sumsq = cc.psum(sumsq, plan.tp_axes, "block/ssm_norm")
        g = rmsnorm_from_sumsq(g, sumsq, cfg.ssm_inner,
                               _lo(ps["norm_scale"]), cfg.norm_eps)
    out = jnp.einsum("bsx,xe->bse", g.astype(xn.dtype),
                     _lo(ps["out"]).reshape(H * Pd, E))
    return out, new_cache


def _head_groups(u, cfg, plan, lay):
    """(B, S, G*N) B or C of every group -> (B, S, H_loc, N), each local
    head's group's, heads H/G to a group.  A shard holds whole groups."""
    G, hl = cfg.ssm_groups, lay.ssm
    per_group = cfg.ssm_heads_ // G
    if hl.hq_pad != hl.n_q or hl.hq_loc % per_group:
        raise ValueError(f"{cfg.name}: tp={plan.tp} splits an SSM group of "
                         f"{per_group} heads across shards")
    g_loc = hl.hq_loc // per_group
    u = u.reshape(u.shape[:2] + (G, -1))
    u = jax.lax.dynamic_slice_in_dim(u, tp_index(plan) * g_loc, g_loc,
                                     axis=2)
    return jnp.repeat(u, per_group, axis=2)


def _paged_ssm(xn, ps, cfg, plan, lay, mode, slab_pool, pages):
    """SSM mixer against the slab pools (paged serving).

    slab_pool: {"statep","conv_xp","conv_Bp","conv_Cp"[,"sscalep"]}, the
    stacked (reps, R, n_slabs, ...) pools the layer loop carries;
    pages["slab_ids"], pages["replica"]: (B,) slab id and replica per
    batch row, pages["layer"]: this layer's index into the stack.  Each
    row gathers its slab of this layer into the per-slot view
    ``ssm_mixer`` expects (conv tails unflattened), runs one decode token
    or one prefill chunk with carried state, and scatters the updated
    state back in place at (layer, replica, slab).  Idle/prefilling
    decode lanes point at the reserved scratch slab (id 0), so full-batch
    decode never corrupts a live slab."""
    at = (pages["layer"], pages["replica"], pages["slab_ids"])
    quant = "sscalep" in slab_pool
    if quant:
        # int8 slabs: dequant through the per-(slab, head) scale on gather,
        # re-quantize the whole slab on scatter (full-overwrite semantics,
        # so the stored bytes depend only on the new state's value)
        state = (slab_pool["statep"][at].astype(jnp.float32) *
                 slab_pool["sscalep"][at][:, :, None, None])
    else:
        state = slab_pool["statep"][at]
    K1 = cfg.ssm_conv - 1
    B = xn.shape[0]
    view = {"state": state}
    for k in ("conv_x", "conv_B", "conv_C"):     # flat channel-major tails
        view[k] = slab_pool[k + "p"][at].reshape(B, -1, K1).swapaxes(1, 2)
    out, new = ssm_mixer(xn, ps, cfg, plan, lay, mode, view,
                         chunk_last_idx=(pages.get("last_idx")
                                         if mode != "decode" else None))
    pools = {k + "p": slab_pool[k + "p"].at[at].set(
        (v if k == "state" else v.swapaxes(1, 2).reshape(B, -1))
        .astype(slab_pool[k + "p"].dtype))
        for k, v in new.items() if not (quant and k == "state")}
    if quant:
        q, s = _row_quant(new["state"])                  # (B,H,P,N), (B,H)
        pools["statep"] = slab_pool["statep"].at[at].set(q)
        pools["sscalep"] = slab_pool["sscalep"].at[at].set(
            s.astype(slab_pool["sscalep"].dtype))
    return out, pools


# ---------------------------------------------------------------------------
# FFN
# ---------------------------------------------------------------------------

def dense_ffn(xn, pf, cfg):
    if cfg.gated_ffn:
        h = activation(jnp.einsum("bse,ef->bsf", xn, _lo(pf["w_gate"])),
                       cfg.act) * jnp.einsum("bse,ef->bsf", xn, _lo(pf["w_up"]))
    else:
        h = activation(jnp.einsum("bse,ef->bsf", xn, _lo(pf["w_up"])), cfg.act)
    return jnp.einsum("bsf,fe->bse", h, _lo(pf["w_down"]))


def ffn_sublayer(xn, pf, cfg, plan, spec):
    """-> (partial output (B,S,E), reduced by the caller's post-FFN psum;
    picks (B,S) on held experts for an expert layer, else None)."""
    if spec.ffn != FFN_MOE:
        with jax.named_scope("mlp"):
            return dense_ffn(xn, pf, cfg), None
    with jax.named_scope("moe"):
        pf_moe = {"router": pf["router"],
                  "experts": jax.tree_util.tree_map(_lo, pf["experts"])}
        if plan.moe_mode == "ep":     # whole experts, a range per shard
            n = cfg.experts_held_ // plan.tp
            first = cfg.expert_first + tp_index(plan) * n
        else:                         # every held expert, F-sliced over tp
            first, n = cfg.expert_first, cfg.experts_held_
        y, picks = moe_ffn(xn, pf_moe, cfg, first, n)
        if cfg.n_shared_experts:
            y = y + dense_ffn(xn, pf["shared"], cfg)
        return y, picks


# ---------------------------------------------------------------------------
# Layer forward (two-sync contract)
# ---------------------------------------------------------------------------

def layer_forward(x, p, cache, cfg, plan, lay, spec, mode, positions,
                  pos=None, enc_memory=None, pages=None):
    """One transformer layer.  Returns (x, new_cache, picks): ``picks``
    (B,S) counts each token's picks on the held experts of an expert
    layer, else None.  Each sublayer runs under a named scope
    ("attn", "mamba", "moe", "mlp") that profiles show."""
    cache = cache or {}
    new_cache = dict(cache)

    def run_attn(h):
        with jax.named_scope("attn"):
            return attn_mixer(h, p["attn"], cfg, plan, lay, spec, mode,
                              cache.get("kv"), positions, pos, pages)

    def run_ssm(h):
        sc = cache.get("ssm")
        with jax.named_scope("mamba"):
            if sc is not None and "statep" in sc:      # slab pools (paged)
                return _paged_ssm(h, p["ssm"], cfg, plan, lay, mode, sc,
                                  pages)
            return ssm_mixer(h, p["ssm"], cfg, plan, lay, mode, sc)

    # ---- mixer sublayer ----------------------------------------------------
    if spec.mixer != MIX_NONE:
        h = apply_norm(x, p["ln1"], cfg)
        if spec.mixer == MIX_ATTN:
            partial, nkv = run_attn(h)
            if nkv is not None:
                new_cache["kv"] = nkv
        elif spec.mixer == MIX_SSM:
            partial, nssm = run_ssm(h)
            if nssm is not None:
                new_cache["ssm"] = nssm
        else:  # hybrid: parallel attn + ssm heads, fused before ONE psum
            pa, nkv = run_attn(h)
            ps_, nssm = run_ssm(h)
            partial = 0.5 * (pa + ps_)
            if nkv is not None:
                new_cache["kv"] = nkv
            if nssm is not None:
                new_cache["ssm"] = nssm
        red = cc.psum(partial, plan.tp_axes, "block/mixer")  # sync #1
        if cfg.sandwich_norm:
            red = apply_norm(red, p["post_ln1"], cfg)
        x = x + red

    # ---- cross-attention sublayer (enc-dec decoders) ------------------------
    if spec.cross_attn:
        h = apply_norm(x, p["ln_x"], cfg)
        partial, ncross = cross_attn_mixer(h, p["xattn"], cfg, plan, lay,
                                           mode, cache.get("cross"),
                                           enc_memory, pages)
        if ncross is not None:
            new_cache["cross"] = ncross
        x = x + cc.psum(partial, plan.tp_axes, "block/xattn")

    # ---- FFN sublayer --------------------------------------------------------
    picks = None
    if spec.ffn != FFN_NONE:
        h = apply_norm(x, p["ln2"], cfg)
        partial, picks = ffn_sublayer(h, p["ffn"], cfg, plan, spec)
        red = cc.psum(partial, plan.tp_axes, "block/ffn")  # sync #2
        if cfg.sandwich_norm:
            red = apply_norm(red, p["post_ln2"], cfg)
        x = x + red

    return x, (new_cache if new_cache else None), picks
