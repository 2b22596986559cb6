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


def layouts():
    """The carried pools are indexed by (layer, replica, page): two
    replicas per data shard (dp=4 on the 2-device data mesh) and heads
    split over a 2-device model axis (tp=2) give the one-device logits for
    every served token, for KV pages and SSM slabs (and, for the
    interleaved Nemotron cut, B/C groups and held experts split over the
    model axis, by their width or whole).  Logits as well as tokens:
    a tiny random model's greedy tokens barely depend on what attention
    reads.  The greedy ids, picked on the device from logits whose
    vocabulary tp=2 splits over the model axis, equal one device's."""
    m1 = make_mesh((1, 1), ("data", "model"), devices=jax.devices()[:1])
    m2 = make_mesh((2, 1), ("data", "model"))
    mt = make_mesh((1, 2), ("data", "model"))

    def run(cfg, plan, mesh, params, dp):
        eng = ServingEngine.build_paged(cfg, plan, mesh, 2, 32, params,
                                        page_size=4, prefill_chunk=8, dp=dp)
        rows, sample = {}, eng._sample_row

        def sample_row(logits, b, req):     # record every row sampled
            rows[req.rid, len(req.out_tokens)] = np.asarray(
                logits[b, :cfg.vocab_size], np.float64)
            return sample(logits, b, req)
        eng._sample_row = sample_row
        rng = np.random.RandomState(0)
        rs = [Request(rid=i, prompt=rng.randint(2, cfg.vocab_size, n)
                      .astype(np.int32), max_new_tokens=5)
              for i, n in enumerate([5, 11, 9, 14, 3, 7, 12, 6, 10, 4])]
        for r in rs:
            eng.submit(r)
        eng.run(max_ticks=3000)
        assert all(r.done for r in rs), [r.rid for r in rs if not r.done]
        assert all(st.routed > 0 for st in eng.stats.replicas), \
            "a replica served nothing"
        # the engine is greedy: each token was picked on the device, and
        # it is the argmax of the row the sampler read
        toks = {r.rid: r.out_tokens for r in rs}
        assert len(rows) == sum(map(len, toks.values())) > 0
        assert eng.stats.device_sampled == len(rows)
        for (rid, i), row in rows.items():
            assert toks[rid][i] == int(np.argmax(row)), (rid, i)
        return rows, toks

    def same(got, want, what):
        (rows, toks), (want_rows, want_toks) = got, want
        assert rows.keys() == want_rows.keys(), what
        for k in want_rows:
            np.testing.assert_allclose(rows[k], want_rows[k], atol=1e-4,
                                       err_msg=f"{what} {k}")
        assert toks == want_toks, f"{what}: greedy ids"

    # tp=2 is compared in float: int8 rounding can turn the psum's other
    # summation order into a different stored byte
    for name, kvd, ssd, tp2 in (("tinyllama-42m", "float32", "", True),
                                ("hymba-1.5b", "int8", "int8", False),
                                ("hymba-1.5b", "float32", "", True),
                                ("nemotron3-nano-ep8", "float32", "", True)):
        cfg = reduced(get_config(name), dtype="float32")
        p1 = ShardingPlan(tp=1, kv_cache_dtype=kvd, ssm_cache_dtype=ssd)
        params = model.init_params(cfg, p1, seed=1)
        oracle = run(cfg, p1, m1, params, 1)
        if not tp2:
            same(run(cfg, p1, m2, params, 4), oracle,
                 f"{name} {kvd}: two replicas per data shard")
            continue
        p2 = ShardingPlan(tp=2, kv_cache_dtype=kvd, ssm_cache_dtype=ssd)
        same(run(cfg, p2, mt, model.init_params(cfg, p2, seed=1, mesh=mt),
                 1), oracle, f"{name} {kvd}: tp=2")
        if name == "tinyllama-42m":
            same(run(cfg, p1, m2, params, 4), oracle,
                 f"{name} {kvd}: two replicas per data shard")
        if cfg.n_experts:       # the held experts whole, one range a shard
            pe = ShardingPlan(tp=2, moe_mode="ep", kv_cache_dtype=kvd)
            same(run(cfg, pe, mt, model.init_params(cfg, pe, seed=1,
                                                    mesh=mt), 1),
                 oracle, f"{name} {kvd}: tp=2, experts by expert parallelism")
    print("paged-layouts OK: dp=4 on 2 shards and tp=2 == 1 device, "
          "logits and greedy ids")
    return 0


if __name__ == "__main__":
    sys.exit(layouts() if sys.argv[1:] == ["--layouts"] else main())
