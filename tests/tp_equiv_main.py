"""TP-equivalence runner (launched in a subprocess with 8 host devices).

Asserts that the paper's partitioning is *exact*: loss, gradients and decode
logits computed on a (data=2, model=4) mesh match the single-device
reference — including GQA kv-duplication, indivisible-head padding, MoE and
SSD sharding.  Run directly:  XLA flags are set below before jax imports.
"""
import os

os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=8")

import dataclasses  # noqa: E402
import sys  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config, reduced  # noqa: E402
from repro.configs.base import ShapeConfig  # noqa: E402
from repro.core import model, steps  # noqa: E402
from repro.core.partition import ShardingPlan  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402


def meshes():
    m1 = make_mesh((1, 1), ("data", "model"), devices=jax.devices()[:1])
    m8 = make_mesh((2, 4), ("data", "model"))
    return m1, m8


def run_case(name, **overrides):
    cfg = reduced(get_config(name), dtype="float32", **overrides)
    B, S = 4, 32
    shape = ShapeConfig("t", "train", S, B)
    m1, m8 = meshes()
    rng = np.random.RandomState(0)
    tokens = jnp.asarray(rng.randint(0, cfg.vocab_size, (B, S)), jnp.int32)
    batch = {"tokens": tokens, "labels": tokens}
    if cfg.is_encdec:
        batch["frames"] = jnp.asarray(rng.randn(B, S, cfg.d_model), jnp.float32)
    if cfg.frontend == "vision_patches":
        batch["image_embeds"] = jnp.asarray(
            rng.randn(B, cfg.n_frontend_embeds, cfg.d_model), jnp.float32)

    losses, decs = [], []
    for mesh, tp in ((m1, 1), (m8, 4)):
        plan = ShardingPlan(tp=tp)
        state = steps.init_train_state(cfg, plan)
        ts, _ = steps.make_train_step(cfg, plan, mesh, shape=shape)
        with jax.set_mesh(mesh):
            st2, stats = jax.jit(ts)(state, batch)
        losses.append(float(stats["loss"]))
        # decode one token from an empty cache
        sshape = ShapeConfig("d", "decode", S, B)
        dec, _, _ = steps.make_decode_step(cfg, plan, mesh, sshape)
        cache = steps.zero_cache_for(cfg, plan, mesh, B, S)
        with mesh:
            lg, _ = jax.jit(dec)(state["params"], cache,
                                 tokens[:, :1], jnp.zeros((B,), jnp.int32))
        lg = np.asarray(jax.device_get(lg)).astype(np.float64)
        decs.append(lg[:, :cfg.vocab_size])

    dl = abs(losses[0] - losses[1])
    rel = dl / max(abs(losses[0]), 1e-9)
    dd = np.max(np.abs(decs[0] - decs[1]))
    ok = rel < 2e-4 and dd < 5e-2
    print(f"{name:25s} loss1={losses[0]:.6f} loss4={losses[1]:.6f} "
          f"rel={rel:.2e} max_dlogit={dd:.2e} {'OK' if ok else 'FAIL'}")
    return ok


def run_cp_case():
    """mamba2 under context parallelism (dp=2 x cp=4) == single device."""
    cfg = reduced(get_config("mamba2-370m"), dtype="float32")
    B, S = 4, 64
    shape = ShapeConfig("t", "train", S, B)
    m1, m8 = meshes()
    rng = np.random.RandomState(0)
    tokens = jnp.asarray(rng.randint(0, cfg.vocab_size, (B, S)), jnp.int32)
    batch = {"tokens": tokens, "labels": tokens}
    losses = []
    for mesh, plan in ((m1, ShardingPlan(tp=1)),
                       (m8, ShardingPlan(tp=1, cp_axes=("model",)))):
        state = steps.init_train_state(cfg, plan)
        ts, _ = steps.make_train_step(cfg, plan, mesh, shape=shape)
        with mesh:
            _, stats = jax.jit(ts)(state, batch)
        losses.append(float(stats["loss"]))
    rel = abs(losses[0] - losses[1]) / max(abs(losses[0]), 1e-9)
    ok = rel < 2e-5
    print(f"{'mamba2-370m (CP 2x4)':25s} loss1={losses[0]:.6f} "
          f"lossCP={losses[1]:.6f} rel={rel:.2e} {'OK' if ok else 'FAIL'}")
    return ok


def main():
    cases = [
        ("qwen3-0.6b", {}),                               # GQA + qk_norm
        ("gemma3-12b", {}),                               # local:global + sandwich
        ("mamba2-370m", {}),                              # SSD
        ("deepseek-moe-16b", {"n_experts": 8, "top_k": 2}),  # MoE (TP slicing)
        ("hymba-1.5b", {"n_heads": 6, "n_kv_heads": 2,
                        "n_layers": 2}),                  # hybrid + head padding
        ("mixtral-8x22b", {"n_experts": 2, "top_k": 1}),  # MoE n_exp < tp
        ("seamless-m4t-large-v2", {}),                    # enc-dec
        ("pixtral-12b", {}),                              # vlm splice
    ]
    ok = True
    for name, ov in cases:
        ok &= run_case(name, **ov)
    ok &= run_cp_case()
    print("ALL-OK" if ok else "SOME-FAILED")
    sys.exit(0 if ok else 1)


if __name__ == "__main__":
    main()
