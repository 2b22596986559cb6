"""Normalization, rotary embeddings, activations, sharded embedding/LM head."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import collectives as cc


# ---------------------------------------------------------------------------
# Norms (computed in f32, cast back)
# ---------------------------------------------------------------------------

def rmsnorm(x, scale, eps=1e-6, denom: int = 0):
    """RMSNorm.  ``denom`` overrides the averaging count (masked/padded dims)."""
    xf = x.astype(jnp.float32)
    n = denom or x.shape[-1]
    ms = jnp.sum(xf * xf, axis=-1, keepdims=True) / n
    y = xf * jax.lax.rsqrt(ms + eps)
    return (y * (1.0 + scale.astype(jnp.float32))).astype(x.dtype)


def rmsnorm_from_sumsq(x, sumsq, n, scale, eps=1e-6):
    """RMSNorm given an externally-reduced sum of squares (cross-shard norm)."""
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(sumsq / n + eps)
    return (y * (1.0 + scale.astype(jnp.float32))).astype(x.dtype)


def layernorm(x, scale, bias, eps=1e-6):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32) + bias.astype(jnp.float32)).astype(x.dtype)


def apply_norm(x, p, cfg):
    if cfg.norm == "layernorm":
        return layernorm(x, p["scale"], p["bias"], cfg.norm_eps)
    return rmsnorm(x, p["scale"], cfg.norm_eps)


def activation(x, kind: str):
    if kind == "gelu":
        return jax.nn.gelu(x, approximate=True)
    if kind == "relu2":
        return jnp.square(jax.nn.relu(x))
    return jax.nn.silu(x)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float):
    return 1.0 / (theta ** (np.arange(0, head_dim, 2, dtype=np.float32) / head_dim))


def apply_rope(x, positions, theta: float):
    """x: (..., S, D) with positions (..., S) or (S,)."""
    if theta <= 0:
        return x
    d = x.shape[-1]
    freqs = jnp.asarray(rope_freqs(d, theta))
    ang = positions[..., None].astype(jnp.float32) * freqs          # (...,S,D/2)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Vocab-sharded embedding / LM head (zero duplication: paper §IV applied to
# the largest tensors in the model)
# ---------------------------------------------------------------------------

def sharded_embed(tokens, table_local, shard_idx, v_loc, axes=("model",), tag="embed"):
    """tokens: (B, S) int32; table_local: (v_loc, E) — this shard's vocab rows.

    Each shard gathers the rows it owns (out-of-range ids hit a zero row) and
    one psum over the TP axis assembles the full embedding.
    """
    offset = shard_idx * v_loc
    local_ids = tokens - offset
    in_range = (local_ids >= 0) & (local_ids < v_loc)
    safe = jnp.clip(local_ids, 0, v_loc - 1)
    emb = jnp.take(table_local, safe, axis=0)
    emb = jnp.where(in_range[..., None], emb, jnp.zeros_like(emb))
    return cc.psum(emb, axes, tag)


def sharded_logits(x, head_local):
    """x: (B, S, E); head_local: (v_loc, E) -> local logits (B, S, v_loc)."""
    return jnp.einsum("bse,ve->bsv", x, head_local)


def sharded_xent(logits_local, labels, shard_idx, v_loc, n_valid_vocab,
                 axes=("model",), tag="loss"):
    """Cross-entropy with vocab-sharded logits.

    logsumexp needs two tiny psums (max + sum-exp); the label logit is
    recovered with a masked gather + psum.  Padded vocab rows are masked.
    """
    lg = logits_local.astype(jnp.float32)
    # mask padded vocab slots (only the last shard has them)
    col = shard_idx * v_loc + jnp.arange(v_loc)
    lg = jnp.where(col < n_valid_vocab, lg, -1e30)
    # max-shift is for numerical stability only: gradient of lse stays exactly
    # softmax when gmax is treated as a constant (pmax has no JVP rule).
    local_max = jax.lax.stop_gradient(jnp.max(lg, axis=-1))
    gmax = cc.psum_max(local_max, axes, tag + "/max")
    gmax = jax.lax.stop_gradient(gmax)
    sumexp = jnp.sum(jnp.exp(lg - gmax[..., None]), axis=-1)
    gsum = cc.psum(sumexp, axes, tag + "/sumexp")
    lse = gmax + jnp.log(gsum)
    local_ids = labels - shard_idx * v_loc
    in_range = (local_ids >= 0) & (local_ids < v_loc)
    safe = jnp.clip(local_ids, 0, v_loc - 1)
    picked = jnp.take_along_axis(lg, safe[..., None], axis=-1)[..., 0]
    picked = jnp.where(in_range, picked, 0.0)
    label_logit = cc.psum(picked, axes, tag + "/label")
    return lse - label_logit                                  # (B, S) nll
