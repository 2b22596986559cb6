"""dp-serving equivalence runner (launched in a subprocess, 2 host devices).

Asserts the replica-sharded page pool is *exact* on a real (data=2,
model=1) mesh: dp=2 serving — each data shard holding only its own
replica's pages — produces greedy outputs token-identical to the
single-device dp=1 oracle, with per-replica leak-freedom.  Run directly:
XLA flags are set below before jax imports.
"""
import os

os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=2")

import sys  # noqa: E402

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config, reduced  # noqa: E402
from repro.core import model  # noqa: E402
from repro.core.partition import ShardingPlan  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402
from repro.serving import Request, ServingEngine  # noqa: E402


def main():
    cfg = reduced(get_config("tinyllama-42m"), dtype="float32")
    plan = ShardingPlan(tp=1, kv_cache_dtype="float32")
    m1 = make_mesh((1, 1), ("data", "model"), devices=jax.devices()[:1])
    m2 = make_mesh((2, 1), ("data", "model"))
    params = model.init_params(cfg, plan)
    rng = np.random.RandomState(0)
    spec = [(rid,
             rng.randint(2, cfg.vocab_size,
                         int(rng.randint(4, 18))).astype(np.int32),
             int(rng.randint(2, 8))) for rid in range(8)]

    def run(mesh, dp):
        eng = ServingEngine.build_paged(
            cfg, plan, mesh, 2, 64, params, page_size=8, prefill_chunk=16,
            prefix_cache=True, dp=dp)
        rs = [Request(rid=r, prompt=p.copy(), max_new_tokens=m)
              for r, p, m in spec]
        for r in rs:
            eng.submit(r)
        eng.run(max_ticks=5000)
        assert all(r.done for r in rs), [r.rid for r in rs if not r.done]
        return eng, {r.rid: tuple(r.out_tokens) for r in rs}

    _, oracle = run(m1, 1)
    eng, got = run(m2, 2)
    assert got == oracle, "dp=2 on a 2-device data mesh diverged from dp=1"
    assert eng.stats.replicas[0].routed > 0 and \
        eng.stats.replicas[1].routed > 0, "router used only one replica"
    for rr in range(2):
        a, c = eng.allocators[rr], eng.prefix_caches[rr]
        assert a.n_free + c.n_cached_pages == a.n_pages - a.n_reserved, \
            f"replica {rr} leaked pages"
    print("dp-equivalence OK: 2-device dp=2 == 1-device dp=1 oracle")
    return 0


if __name__ == "__main__":
    sys.exit(main())
