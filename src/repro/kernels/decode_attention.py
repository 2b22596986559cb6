"""Flash-decode kernel — the paper's autoregressive (GEMV) hot spot on TPU.

One new token attends to a long KV cache: arithmetic intensity ~1 FLOP/byte,
purely HBM-bandwidth-bound (the TPU analog of the paper's L3-bound GEMV
regime).  The kernel streams K/V through VMEM in (bkv, D) tiles on the
sequential grid axis with online-softmax scratch carries, touching each
cache byte exactly once; batch*heads ride the parallel grid axes.  Length
masking handles ragged prefixes (continuous batching).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -1e30


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref,
                   l_ref, *, scale, bkv, n_kv):
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG)
        l_ref[...] = jnp.zeros_like(l_ref)

    length = len_ref[0]

    @pl.when(ki * bkv < length)
    def _compute():
        s = jax.lax.dot_general(
            q_ref[0, 0][None], k_ref[0, 0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale      # (1, bkv)
        kpos = ki * bkv + jax.lax.broadcasted_iota(jnp.int32, (1, bkv), 1)
        mask = kpos < length
        s = jnp.where(mask, s, NEG)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s))
        p = jnp.exp(s - m_new) * mask
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0, 0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)[0]
        m_ref[...] = m_new

    @pl.when(ki == n_kv - 1)
    def _done():
        o_ref[0, 0] = (acc_ref[...] /
                       jnp.maximum(l_ref[...], 1e-20)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "bkv", "interpret"))
def decode_attention(q, k, v, length, *, scale=None, bkv=512,
                     interpret=False):
    """q: (B, H, D); k/v: (B, H, S, D); length: (B,) -> (B, H, D)."""
    B, H, D = q.shape
    S = k.shape[2]
    scale = scale if scale is not None else D ** -0.5
    bkv = min(bkv, S)
    pkv = (-S) % bkv
    if pkv:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pkv), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pkv), (0, 0)))
    n_kv = (S + pkv) // bkv
    grid = (B, H, n_kv)
    return pl.pallas_call(
        functools.partial(_decode_kernel, scale=scale, bkv=bkv, n_kv=n_kv),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1,), lambda b, h, j: (b,),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, D), lambda b, h, j: (b, h, 0)),
            pl.BlockSpec((1, 1, bkv, D), lambda b, h, j: (b, h, j, 0)),
            pl.BlockSpec((1, 1, bkv, D), lambda b, h, j: (b, h, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, D), lambda b, h, j: (b, h, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((D,), jnp.float32),
            pltpu.VMEM((), jnp.float32),
            pltpu.VMEM((), jnp.float32),
        ],
        interpret=interpret,
    )(length, q, k, v)


# ---------------------------------------------------------------------------
# Paged decode / verify: K/V pages streamed through a scalar-prefetched
# block table.  Decode is the one-query case of verify (query 0 sees
# positions < length), so both share one kernel.
# ---------------------------------------------------------------------------

def _paged_kernel(bt_ref, len_ref, q_ref, k_ref, v_ref, *refs, scale, psz,
                  n_max, nq, quant):
    if quant:
        ks_ref, vs_ref, o_ref, acc_ref, m_ref, l_ref = refs
    else:
        o_ref, acc_ref, m_ref, l_ref = refs
    b = pl.program_id(0)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG)
        l_ref[...] = jnp.zeros_like(l_ref)

    length = len_ref[b]

    # query i sees positions < length + i; the deepest query gates the page
    @pl.when(ki * psz < length + nq - 1)
    def _compute():
        q, k, v = q_ref[0, 0], k_ref[0, 0], v_ref[0, 0]
        if quant:
            q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale      # (nq, psz)
        if quant:
            # int8 rows dequantize through the page's per-row scales,
            # applied to the (nq, psz) scores rather than the (psz, D) block
            s = s * ks_ref[0]
        kpos = ki * psz + jax.lax.broadcasted_iota(jnp.int32, (nq, psz), 1)
        qpos = jax.lax.broadcasted_iota(jnp.int32, (nq, psz), 0)
        mask = kpos < length + qpos
        s = jnp.where(mask, s, NEG)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None]) * mask
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1)
        pv = p * vs_ref[0] if quant else p.astype(v.dtype)
        acc_ref[...] = acc_ref[...] * corr[:, None] + jax.lax.dot_general(
            pv, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ki == n_max - 1)
    def _done():
        o_ref[0, 0] = (acc_ref[...] /
                       jnp.maximum(l_ref[...], 1e-20)[:, None]
                       ).astype(o_ref.dtype)


def _paged_attention(q, k_pages, v_pages, block_table, length, scale,
                     interpret, k_scale, v_scale):
    """q: (B, H, Q, D) -> (B, H, Q, D); see ``paged_verify_attention``."""
    B, H, nq, D = q.shape
    n_pages, Hk, psz, _ = k_pages.shape
    assert Hk == H, (Hk, H)
    n_max = block_table.shape[1]
    scale = scale if scale is not None else D ** -0.5
    quant = k_scale is not None
    # every block's two minor dims equal the array's own (Q, D) / (psz, D)
    # / (1, psz): the TPU tiling accepts such blocks at any extent
    in_specs = [
        pl.BlockSpec((1, 1, nq, D), lambda b, h, j, bt, ln: (b, h, 0, 0)),
        pl.BlockSpec((1, 1, psz, D),
                     lambda b, h, j, bt, ln: (bt[b, j], h, 0, 0)),
        pl.BlockSpec((1, 1, psz, D),
                     lambda b, h, j, bt, ln: (bt[b, j], h, 0, 0)),
    ]
    inputs = (block_table, length, q, k_pages, v_pages)
    if quant:
        assert k_pages.dtype == jnp.int8, k_pages.dtype
        in_specs += [
            pl.BlockSpec((1, 1, psz), lambda b, h, j, bt, ln: (bt[b, j], 0, 0)),
            pl.BlockSpec((1, 1, psz), lambda b, h, j, bt, ln: (bt[b, j], 0, 0)),
        ]
        inputs += (k_scale.reshape(n_pages, 1, psz),
                   v_scale.reshape(n_pages, 1, psz))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, H, n_max),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, nq, D),
                               lambda b, h, j, bt, ln: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((nq, D), jnp.float32),
            pltpu.VMEM((nq,), jnp.float32),
            pltpu.VMEM((nq,), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(_paged_kernel, scale=scale, psz=psz, n_max=n_max,
                          nq=nq, quant=quant),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, nq, D), q.dtype),
        interpret=interpret,
    )(*inputs)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def paged_decode_attention(q, k_pages, v_pages, block_table, length, *,
                           scale=None, interpret=False,
                           k_scale=None, v_scale=None):
    """Decode attention over a paged KV pool.

    q: (B, H, D); k_pages/v_pages: (n_pages, H, psz, D);
    block_table: (B, n_max) int32 page ids; length: (B,) -> (B, H, D).
    ``k_scale``/``v_scale`` ((n_pages, psz) float32) select the int8
    dequant-on-read variant.

    ``length`` counts valid tokens (positions < length attend), matching the
    contiguous kernel above — NOT the inclusive current-position convention
    of ``core.attention`` decode paths.  When driving this from the engine's
    ``pos`` array (position of the just-written token), pass ``pos + 1``.

    The sequential grid axis walks each sequence's block table; the page id
    is scalar-prefetched so the next page's DMA is issued with the gathered
    address — no materialized contiguous copy of the cache (the same
    minimal-off-chip-traffic discipline as the paper's L3-resident GEMV,
    with the pool standing in for on-chip K/V).
    """
    return _paged_attention(q[:, :, None], k_pages, v_pages, block_table,
                            length, scale, interpret, k_scale,
                            v_scale)[:, :, 0]


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def paged_verify_attention(q, k_pages, v_pages, block_table, length, *,
                           scale=None, interpret=False,
                           k_scale=None, v_scale=None):
    """Verify attention over a paged KV pool: Q queries per slot in one pass.

    q: (B, H, Q, D) — query i of slot b sits at absolute position
    ``length[b] - 1 + i`` (query 0 is the last accepted token, queries
    1..Q-1 are drafted tokens whose KV the caller already wrote);
    k_pages/v_pages: (n_pages, H, psz, D); block_table: (B, n_max);
    length: (B,) valid tokens ahead of query 0 (pass ``pos + 1``, as in
    ``paged_decode_attention``) -> (B, H, Q, D).

    Per-query masking ``kpos < length + qpos`` gives each draft query its
    causal prefix (draft j's KV sits at stream position length - 1 + j).
    Same page streaming as the decode kernel — each cache byte still moves
    off-chip once per step, now amortized over Q scored positions: the
    bandwidth-bound speculation argument.
    """
    return _paged_attention(q, k_pages, v_pages, block_table, length, scale,
                            interpret, k_scale, v_scale)
