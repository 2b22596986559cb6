"""Greedy reference for the serving engine's outputs.

``greedy_reference`` generates each request on its own, straight from the
contiguous compiled steps (``core.steps.make_prefill_step`` over the exact
prompt, then ``make_decode_step``), both at batch 1.  No scheduler, slot,
page or chunk is involved, so the paged engine's greedy tokens can be
checked against it.  It stops where the engine does (``engine._emit``):
at ``eos_id``, after ``max_new_tokens``, or once the position reaches
``seq_budget - 1``.
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ShapeConfig
from repro.core import steps


def greedy_reference(cfg, plan, mesh, params, requests, seq_budget,
                     eos_id=1):
    """-> {rid: tuple of greedy tokens} for ``requests`` (objects with
    ``rid``, ``prompt``, ``max_new_tokens`` and, for enc-dec archs,
    ``frames``)."""
    pre, _, _ = steps.make_prefill_step(
        cfg, plan, mesh, ShapeConfig("ref_p", "decode", seq_budget, 1))
    dec, _, _ = steps.make_decode_step(
        cfg, plan, mesh, ShapeConfig("ref_d", "decode", seq_budget, 1))
    pre, dec = jax.jit(pre), jax.jit(dec)

    V = cfg.vocab_size

    def pick(logits):
        return int(np.argmax(np.asarray(logits, np.float32)[0, :V]))

    out = {}
    for req in requests:
        cache = steps.zero_cache_for(cfg, plan, mesh, 1, seq_budget)
        prompt = jnp.asarray(np.asarray(req.prompt, np.int32)[None])
        with mesh:
            if cfg.is_encdec:
                frames = jnp.asarray(np.asarray(req.frames, np.float32)[None],
                                     jnp.dtype(cfg.dtype))
                logits, cache = pre(params, frames, prompt, cache)
            else:
                logits, cache = pre(params, prompt, cache)
        pos, toks = len(req.prompt), [pick(logits)]
        while toks[-1] != eos_id and len(toks) < req.max_new_tokens \
                and pos < seq_budget - 1:
            with mesh:
                logits, cache = dec(params, cache,
                                    jnp.asarray([[toks[-1]]], jnp.int32),
                                    jnp.asarray([pos], jnp.int32))
            pos += 1                  # the decode step wrote toks[-1]'s KV
            toks.append(pick(logits))
        out[req.rid] = tuple(toks)
    return out
