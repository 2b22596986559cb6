"""ZeRO-1 equivalence runner (8 host devices): the sharded-optimizer train
step must follow the identical loss trajectory as the replicated-optimizer
step on the same (data=2, model=4) mesh."""
import os

os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=8")

import sys  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config, reduced  # noqa: E402
from repro.configs.base import ShapeConfig  # noqa: E402
from repro.core import steps  # noqa: E402
from repro.core.partition import ShardingPlan  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402


def main():
    cfg = reduced(get_config("qwen3-0.6b"), dtype="float32")
    B, S = 4, 32
    shape = ShapeConfig("t", "train", S, B)
    mesh = make_mesh((2, 4), ("data", "model"))
    plan = ShardingPlan(tp=4)
    rng = np.random.RandomState(0)
    batches = []
    for _ in range(3):
        t = jnp.asarray(rng.randint(0, cfg.vocab_size, (B, S)), jnp.int32)
        batches.append({"tokens": t, "labels": t})

    state = steps.init_train_state(cfg, plan)
    ts = jax.jit(steps.make_train_step(cfg, plan, mesh, shape=shape)[0])
    ls = []
    with mesh:
        for b in batches:
            state, st = ts(state, b)
            ls.append(float(st["loss"]))

    plan1 = plan.with_(zero1=True)
    state1 = steps.init_train_state_zero1(cfg, plan1, mesh)
    t1 = jax.jit(steps.make_train_step_zero1(cfg, plan1, mesh,
                                             shape=shape)[0])
    l1 = []
    with mesh:
        for b in batches:
            state1, st = t1(state1, b)
            l1.append(float(st["loss"]))

    rel = max(abs(a - b) / abs(a) for a, b in zip(ls, l1, strict=True))
    print(f"std={ls} zero1={l1} rel={rel:.2e}")
    print("ZERO1-OK" if rel < 1e-4 else "ZERO1-FAIL")
    sys.exit(0 if rel < 1e-4 else 1)


if __name__ == "__main__":
    main()
