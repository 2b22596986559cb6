"""Decode == prefill consistency: token-by-token decoding with the KV/SSM
cache must reproduce the teacher-forced (prefill) logits."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.configs.base import ShapeConfig
from repro.core import model, steps
from repro.core.partition import ShardingPlan, model_layout

PLAN = ShardingPlan(tp=1)
B, S = 2, 48


@pytest.mark.parametrize("name", ["qwen3-0.6b", "mamba2-370m", "gemma3-12b",
                                  "hymba-1.5b", "mixtral-8x22b"])
def test_decode_matches_prefill(name, mesh1):
    cfg = reduced(get_config(name), dtype="float32")
    lay = model_layout(cfg, PLAN)
    params = model.init_params(cfg, PLAN)
    rng = np.random.RandomState(0)
    tokens = jnp.asarray(rng.randint(0, cfg.vocab_size, (B, S)), jnp.int32)

    shape = ShapeConfig("d", "decode", S + 1, B)
    dec, _, _ = steps.make_decode_step(cfg, PLAN, mesh1, shape)
    dec = jax.jit(dec)
    cache = steps.zero_cache_for(cfg, PLAN, mesh1, B, S + 1)

    # teacher-forced full forward (train-mode logits at every position)
    def full(params, tokens):
        positions = jnp.broadcast_to(jnp.arange(S), (B, S))
        from repro.core.layers import apply_norm
        x = model.embed_tokens(params, tokens, cfg, PLAN, lay)
        x, _ = model._run_stack(x, params["stacks"], cfg.layer_groups(), cfg,
                                PLAN, lay, "train", positions)
        x = apply_norm(x, params["final_norm"], cfg)
        return model.final_logits(params, x, cfg, lay)

    from jax.sharding import PartitionSpec as P

    pspecs = model.param_pspecs(cfg, PLAN)
    full_fn = jax.jit(jax.shard_map(
        full, mesh=mesh1, in_specs=(pspecs, P(None, None)),
        out_specs=P(None, None, "model"), check_vma=False))
    with mesh1:
        ref_logits = np.asarray(full_fn(params, tokens), np.float64)

    got = np.zeros_like(ref_logits)
    with mesh1:
        for t in range(S):
            lg, cache = dec(params, cache, tokens[:, t:t + 1],
                            jnp.full((B,), t, jnp.int32))
            got[:, t] = np.asarray(lg, np.float64)

    # tolerance: decode and teacher-forced paths use different reduction
    # orders (flash decode vs chunked flash); gemma's sqrt(E) embed scaling
    # amplifies absolute logit noise — errors are flat in position (no cache
    # drift).  MoE archs additionally have DISCONTINUOUS routing: ~1e-3
    # numeric noise can flip a top-k tie at isolated positions, producing
    # large but sparse deltas — so MoE asserts on the 99th percentile.
    err = np.abs(got - ref_logits)
    if cfg.n_experts:
        # audited: isolated flip (e.g. one position), no drift, full recovery
        assert float(np.median(err)) < 2e-3, np.median(err)
        assert float(err.max()) < 0.2, err.max()
        assert float((err > 0.1).mean()) < 1e-3
    else:
        np.testing.assert_allclose(got, ref_logits, rtol=6e-3, atol=6e-3)
