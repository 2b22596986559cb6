"""Attention: chunked flash (prefill/train) + single-token decode.

Pure-JAX implementations used for CPU validation and for the 512-device
dry-run lowering (XLA:TPU fuses these well); the Pallas kernels in
``repro.kernels`` implement the same contracts for real-TPU execution and
are validated against ``repro.kernels.ref`` which in turn matches these.

Shapes follow the per-shard grouped-GQA layout from ``partition.head_layout``:

    q: (B, G, R, Sq, D)   — G local kv slots, R q-heads per slot
    k/v: (B, G, Skv, D)

Sliding-window attention slices the kv stream (linear cost); full causal
attention scans all kv chunks with masking (the known 2x upper-triangle
overhead of scan-based flash — eliminated in the Pallas kernel via grid
pruning and accounted for explicitly in the roofline analytics).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import collectives as cc
from repro.core.kvcache import page_rows

NEG = -1e30


def _online_chunk(acc, m, den, q, k, v, mask, scale, softcap=0.0):
    """One online-softmax update.  q:(...,R,Sq,D) k:(...,C,D) mask:(...,Sq,C)."""
    s = jnp.einsum("...rsd,...cd->...rsc", q, k,
                   preferred_element_type=jnp.float32) * scale
    if softcap:
        s = softcap * jnp.tanh(s / softcap)
    s = jnp.where(mask[..., None, :, :], s, NEG)
    m_new = jnp.maximum(m, jnp.max(s, axis=-1))
    p = jnp.exp(s - m_new[..., None]) * mask[..., None, :, :]
    corr = jnp.exp(m - m_new)
    den_new = den * corr + jnp.sum(p, axis=-1)
    pv = jnp.einsum("...rsc,...cd->...rsd", p.astype(v.dtype), v,
                    preferred_element_type=jnp.float32)
    acc_new = acc * corr[..., None] + pv
    return acc_new, m_new, den_new


def flash_attention(q, k, v, *, causal=True, window=0, softcap=0.0,
                    scale=None, q_offset=0, kv_offset=0,
                    q_block=512, kv_block=512):
    """Chunked attention.  Returns (B, G, R, Sq, D) in q.dtype."""
    B, G, R, Sq, D = q.shape
    Skv = k.shape[2]
    scale = scale if scale is not None else D ** -0.5
    q_block = min(q_block, Sq)
    kv_block = min(kv_block, Skv)

    # pad sequences to block multiples
    Sq_p = -(-Sq // q_block) * q_block
    Skv_p = -(-Skv // kv_block) * kv_block
    if Sq_p != Sq:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, 0), (0, Sq_p - Sq), (0, 0)))
    if Skv_p != Skv:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, Skv_p - Skv), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, Skv_p - Skv), (0, 0)))

    nq = Sq_p // q_block
    q_blocked = q.reshape(B, G, R, nq, q_block, D)

    windowed = causal and window > 0 and Skv_p > window + q_block
    if windowed:
        # slice length covering [q_end - window, q_end) for the whole q block
        L = min(Skv_p, -(-(window + q_block) // kv_block) * kv_block)
    else:
        L = Skv_p
    n_kv = L // kv_block

    def one_q_block(qi, qb):  # qb: (B, G, R, q_block, D)
        q_pos = q_offset + qi * q_block + jnp.arange(q_block)
        if windowed:
            # k-array index of the window start for this q block
            start = jnp.clip(q_offset + (qi + 1) * q_block - L - kv_offset,
                             0, Skv_p - L)
            ks = jax.lax.dynamic_slice_in_dim(k, start, L, axis=2)
            vs = jax.lax.dynamic_slice_in_dim(v, start, L, axis=2)
            kv_base = kv_offset + start
        else:
            ks, vs, kv_base = k, v, kv_offset

        def kv_step(carry, c):
            acc, m, den = carry
            kc = jax.lax.dynamic_slice_in_dim(ks, c * kv_block, kv_block, axis=2)
            vc = jax.lax.dynamic_slice_in_dim(vs, c * kv_block, kv_block, axis=2)
            kv_pos = kv_base + c * kv_block + jnp.arange(kv_block)
            mask = jnp.ones((q_block, kv_block), bool)
            mask &= (kv_pos[None, :] < kv_offset + Skv)        # kv padding
            if causal:
                mask &= kv_pos[None, :] <= q_pos[:, None]
            if window > 0:
                mask &= kv_pos[None, :] > q_pos[:, None] - window
            mask = jnp.broadcast_to(mask, (B, G, q_block, kv_block))
            return _online_chunk(acc, m, den, qb, kc, vc, mask, scale,
                                 softcap), None

        acc0 = jnp.zeros((B, G, R, q_block, D), jnp.float32)
        m0 = jnp.full((B, G, R, q_block), NEG, jnp.float32)
        l0 = jnp.zeros((B, G, R, q_block), jnp.float32)
        (acc, m, den), _ = jax.lax.scan(kv_step, (acc0, m0, l0),
                                        jnp.arange(n_kv))
        return acc / jnp.maximum(den, 1e-20)[..., None]

    out = jax.lax.map(lambda args: one_q_block(*args),
                      (jnp.arange(nq), jnp.moveaxis(q_blocked, 3, 0)))
    out = jnp.moveaxis(out, 0, 3).reshape(B, G, R, Sq_p, D)
    return out[:, :, :, :Sq].astype(q.dtype)


def flash_attention_split(q, k, v, *, window=0, softcap=0.0, scale=None,
                          q_block=512, kv_block=512, depth=3, q_offset=0):
    """Recursive causal splitting (beyond-paper §Perf optimization).

    The scan-based flash pays ~2x FLOPs on full-causal attention (every q
    block visits every kv chunk, half masked).  Split the q range: the upper
    half genuinely needs the full kv prefix; the lower half only needs the
    first half of kv — a STATIC slice, so recursion is compile-time.  Cost
    converges to (2/3) S^2 vs S^2 (waste 4/3 instead of 2) at depth >= 3.
    Exact — validated against the ref oracle in tests.
    """
    Sq = q.shape[3]
    if depth <= 0 or Sq < 4 * q_block:
        return flash_attention(q, k, v, causal=True, window=window,
                               softcap=softcap, scale=scale,
                               q_offset=q_offset, q_block=q_block,
                               kv_block=kv_block)
    half = Sq // 2
    o_hi = flash_attention(q[:, :, :, half:], k, v, causal=True,
                           window=window, softcap=softcap, scale=scale,
                           q_offset=q_offset + half, q_block=q_block,
                           kv_block=kv_block)
    o_lo = flash_attention_split(
        q[:, :, :, :half], k[:, :, :q_offset + half],
        v[:, :, :q_offset + half], window=window, softcap=softcap,
        scale=scale, q_block=q_block, kv_block=kv_block, depth=depth - 1,
        q_offset=q_offset)
    return jnp.concatenate([o_lo, o_hi], axis=3)


# ---------------------------------------------------------------------------
# Decode (one new token against a cache)
# ---------------------------------------------------------------------------

def decode_attention(q, k_cache, v_cache, slot_pos, cur_pos, *, window=0,
                     softcap=0.0, scale=None, seq_axes=(), tag="attn/decode"):
    """q: (B, G, R, D); caches: (B, G, S_slots, D); slot_pos: (B, S_slots)
    absolute position held by each slot (-1 = empty).  ``seq_axes``: mesh axes
    the cache sequence dim is sharded over (long-context distributed
    flash-decode: partial (m, l, acc) merged with an LSE-weighted psum —
    the paper's partial-output hierarchical reduction applied to sequence).
    """
    B, G, R, D = q.shape
    scale = scale if scale is not None else D ** -0.5
    kf = k_cache
    s = jnp.einsum("bgrd,bgsd->bgrs", q, kf,
                   preferred_element_type=jnp.float32) * scale
    if softcap:
        s = softcap * jnp.tanh(s / softcap)
    valid = (slot_pos >= 0) & (slot_pos <= cur_pos[:, None])
    if window > 0:
        valid &= slot_pos > (cur_pos[:, None] - window)
    s = jnp.where(valid[:, None, None, :], s, NEG)
    m = jnp.max(s, axis=-1)
    p = jnp.exp(s - m[..., None]) * valid[:, None, None, :]
    den = jnp.sum(p, axis=-1)
    acc = jnp.einsum("bgrs,bgsd->bgrd", p.astype(v_cache.dtype), v_cache,
                     preferred_element_type=jnp.float32)
    if seq_axes:
        gm = cc.psum_max(m, seq_axes, tag + "/m")
        w = jnp.exp(m - gm)
        den = cc.psum(den * w, seq_axes, tag + "/l")
        acc = cc.psum(acc * w[..., None], seq_axes, tag + "/acc")
    out = acc / jnp.maximum(den, 1e-20)[..., None]
    return out.astype(q.dtype)


# ---------------------------------------------------------------------------
# Paged KV (block-table indirection over a page pool)
# ---------------------------------------------------------------------------

def _rows(pool, rows, at):
    """Rows ``rows`` (B, n) of a pool's page axis.  With ``at`` = (layer,
    replica (B,)) the pool is the stacked ``(reps, R, n_pages, ...)`` state
    of the layer loop, and row b is gathered from (layer, replica[b])
    straight out of it, so no layer's or replica's pool is ever sliced
    out whole."""
    if at is None:
        return pool[rows]
    layer, replica = at
    return pool[layer, replica[:, None], rows]


def gather_pages(pool, block_table, at=None):
    """Materialize each slot's logical KV stream from the page pool.

    pool: (n_pages, psz, G, D) token-major pages, or the stacked
    (reps, R, n_pages, psz, G, D) pools with ``at`` = (layer, replica of
    each row); block_table: (B, n_max) int32 page ids.
    -> (B, G, n_max * psz, D).  Pure-JAX gather: the Pallas kernel in
    ``repro.kernels.decode_attention`` streams pages via scalar-prefetched
    block tables instead of materializing this copy.
    """
    psz, G, D = pool.shape[-3:]
    B, n_max = block_table.shape
    g = _rows(pool, block_table, at)                  # (B,n_max,psz,G,D)
    return g.reshape(B, n_max * psz, G, D).transpose(0, 2, 1, 3)


def gather_pages_dequant(pool, scales, block_table, dtype, at=None):
    """``gather_pages`` for an int8 pool with per-(page, slot) scales.

    pool: (n_pages, psz, G, D) int8; scales: (n_pages * psz,) float32 —
    one scale per token row, slot o of page p at p * psz + o, written
    atomically with the payload by ``blocks._page_write`` / the cross-KV
    write step (both stacked like the pool when ``at`` is given).
    -> (B, G, n_max * psz, D) in ``dtype``.
    """
    B, n_max = block_table.shape
    psz = pool.shape[-3]
    g = gather_pages(pool, block_table, at).astype(jnp.float32)
    s = _rows(scales, page_rows(block_table, psz), at)    # (B, n_max*psz)
    s = s.reshape(B, 1, n_max * psz, 1)
    return (g * s).astype(dtype)


def gather_kv(k_pool, v_pool, block_table, dtype, *, at=None,
              k_scale=None, v_scale=None):
    """-> the (K, V) streams, each (B, G, n_max * psz, D) in ``dtype``:
    int8 pools dequantized through their per-row scales, float pools cast
    after the gather, so only the pages the table names are converted."""
    if k_scale is not None:
        return (gather_pages_dequant(k_pool, k_scale, block_table, dtype, at),
                gather_pages_dequant(v_pool, v_scale, block_table, dtype, at))
    return (gather_pages(k_pool, block_table, at).astype(dtype),
            gather_pages(v_pool, block_table, at).astype(dtype))


def paged_decode_attention(q, k_pool, v_pool, block_table, cur_pos, *,
                           window=0, softcap=0.0, scale=None,
                           k_scale=None, v_scale=None, at=None):
    """Decode attention reading K/V through a block table.

    q: (B, G, R, D); pools: (n_pages, psz, G, D), or stacked with ``at``
    as in ``gather_pages``; block_table: (B, n_max); cur_pos: (B,) absolute
    position of the current token.  Slot s of the gathered stream holds
    absolute position s by construction, so validity is simply s <=
    cur_pos (plus the sliding window).

    ``k_scale``/``v_scale`` ((n_pages * psz,) float32): int8 pools are
    dequantized through their per-row scales before scoring.
    """
    B = q.shape[0]
    L = block_table.shape[1] * k_pool.shape[-3]
    kv_pos = jnp.broadcast_to(jnp.arange(L, dtype=jnp.int32), (B, L))
    kf, vf = gather_kv(k_pool, v_pool, block_table, q.dtype, at=at,
                       k_scale=k_scale, v_scale=v_scale)
    return decode_attention(q, kf, vf, kv_pos,
                            cur_pos, window=window, softcap=softcap,
                            scale=scale, tag="attn/paged_decode")


def paged_verify_attention(q, k_pool, v_pool, block_table, cur_pos, *,
                           window=0, softcap=0.0, scale=None,
                           k_scale=None, v_scale=None, at=None):
    """Q-query decode attention for speculative verify.

    q: (B, G, R, Q, D) — per slot, query i sits at absolute position
    ``cur_pos + i`` (query 0 is the slot's last accepted token; queries
    1..Q-1 are drafted tokens whose KV the caller wrote this step).
    Pools/block_table as in ``paged_decode_attention``; cur_pos: (B,).

    Validity generalizes decode's ``s <= cur_pos`` per query:
    ``kv_pos <= cur_pos + i`` — the causal mask inside the draft block
    falls out of it, since draft j's KV sits at position cur_pos + j.
    One gather and one batched score pass serve all Q queries, so the
    pools stream off-chip once per verify step instead of once per token
    (the bandwidth argument for speculation on a memory-bound decode).
    """
    B, G, R, Q, D = q.shape
    scale = scale if scale is not None else D ** -0.5
    L = block_table.shape[1] * k_pool.shape[-3]
    kf, vf = gather_kv(k_pool, v_pool, block_table, q.dtype, at=at,
                       k_scale=k_scale, v_scale=v_scale)
    s = jnp.einsum("bgrqd,bgsd->bgrqs", q, kf,
                   preferred_element_type=jnp.float32) * scale
    if softcap:
        s = softcap * jnp.tanh(s / softcap)
    kv_pos = jnp.arange(L, dtype=jnp.int32)[None, None, :]   # (1, 1, L)
    q_pos = cur_pos[:, None, None] + jnp.arange(Q)[None, :, None]  # (B,Q,1)
    valid = kv_pos <= q_pos                                  # (B, Q, L)
    if window > 0:
        valid &= kv_pos > q_pos - window
    s = jnp.where(valid[:, None, None], s, NEG)
    m = jnp.max(s, axis=-1)
    p = jnp.exp(s - m[..., None]) * valid[:, None, None]
    den = jnp.sum(p, axis=-1)
    acc = jnp.einsum("bgrqs,bgsd->bgrqd", p.astype(vf.dtype), vf,
                     preferred_element_type=jnp.float32)
    out = acc / jnp.maximum(den, 1e-20)[..., None]
    return out.astype(q.dtype)
