"""Mixture-of-Experts FFN.

One routed layer, ``moe_ffn``, for both distribution modes
(``plan.moe_mode``); they differ only in which experts a shard computes:

* ``tp`` — **paper-faithful**: every expert's intermediate dimension is
  sliced across the model axis exactly like a dense FC layer (the paper's
  F-slicing applied per expert).  No weight duplication, no extra
  collectives: routed partial outputs fold into the block's single post-FFN
  psum.  This is the only zero-duplication option when
  ``n_experts < tp`` (mixtral: 8 experts on 16 shards).
* ``ep`` — beyond-paper expert parallelism: experts stored whole,
  ``experts_held_ / tp`` per shard.  With replicated activations (the
  paper's layout) no all_to_all is needed: every shard routes all tokens,
  runs only its own experts and emits a partial combine that folds into
  the same post-FFN psum — fewer, larger matmuls (MXU-friendlier).

A layer may hold only a range of the experts (``cfg.expert_first``,
``cfg.experts_held``), as one chip of an expert-parallel deployment does:
it routes over all ``n_experts`` and returns the held experts' part.

Routing drops nothing.  Each computed expert runs as one dense bucket
whose capacity is the step's token count, at the token's own row (a token
picks an expert at most once); rows of tokens that did not pick it are
weighted by zero.  So a token's output never depends on its batch-mates,
at any batch or chunk size, at the cost of computing every token's row
for every computed expert.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.layers import activation


def route(xt, router, cfg):
    """Top-``cfg.top_k`` routing over all ``cfg.n_experts``.
    x: (T, E) -> (gates (T,k) f32, idx (T,k) i32).

    ``cfg.router == "softmax"``: the top k of softmax(x . w), renormalised.
    ``"sigmoid"``: scores sigmoid(x . w); the top k of score + the
    correction bias are picked, and their scores, renormalised to sum to
    1, are scaled by ``cfg.routed_scale``.  The bias steers the choice
    only, never the weights."""
    logits = jnp.einsum("te,en->tn", xt.astype(jnp.float32),
                        router["w"].astype(jnp.float32))
    if cfg.router == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        _, idx = jax.lax.top_k(scores + router["bias"].astype(jnp.float32),
                               cfg.top_k)
        gates = jnp.take_along_axis(scores, idx, axis=-1)
        gates = gates / (jnp.sum(gates, axis=-1, keepdims=True) + 1e-20)
        return gates * cfg.routed_scale, idx
    gates, idx = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), cfg.top_k)
    gates = gates / jnp.maximum(jnp.sum(gates, axis=-1, keepdims=True), 1e-9)
    return gates, idx


def moe_ffn(x, p, cfg, first, n: int):
    """Routed FFN over the experts ``[first, first + n)`` that this shard
    computes (``first`` may be traced; ``p["experts"]`` holds those ``n``).

    Returns the part of the result those experts give: each token's picks
    that fall on them, weighted by their gates; picks on other experts are
    another shard's or chip's part and are left out.
    x: (B, S, E) -> (partial output (B, S, E), picks (B, S) int32: each
    token's picks on the layer's held experts, ``cfg.expert_first`` to
    ``cfg.expert_first + cfg.experts_held_``, the same on every shard)."""
    B, S, E = x.shape
    xt = x.reshape(B * S, E)
    gates, idx = route(xt, p["router"], cfg)
    local = idx - first
    mine = (local >= 0) & (local < n)                        # (T, k)
    # (T, n): each computed expert's gate for each token, 0 where not picked
    comb = jnp.sum(jnp.where(mine[..., None] & (local[..., None] ==
                                                jnp.arange(n)),
                             gates[..., None], 0.0), axis=1)
    ex = p["experts"]
    up = jnp.einsum("te,nef->ntf", xt, ex["w_up"])
    if cfg.gated_ffn:
        h = activation(jnp.einsum("te,nef->ntf", xt, ex["w_gate"]),
                       cfg.act) * up
    else:
        h = activation(up, cfg.act)
    out = jnp.einsum("ntf,nfe->nte", h, ex["w_down"])
    y = jnp.sum(out.astype(jnp.float32) * comb.T[..., None], axis=0)
    held = (idx >= cfg.expert_first) & \
        (idx < cfg.expert_first + cfg.experts_held_)
    picks = jnp.sum(held, axis=-1, dtype=jnp.int32).reshape(B, S)
    return y.astype(x.dtype).reshape(B, S, E), picks
