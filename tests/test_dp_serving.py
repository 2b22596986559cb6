"""dp>1 serving: replica-sharded page pools, replica-local allocators /
prefix caches / schedulers, the request router (prefix affinity + least
page load), and the dp=2 engine's equivalence to the dp=1 oracle —
token-identical greedy outputs, per-replica conservation / leak-freedom
under forced preemption across all three policies, and replica-aware
drain."""
import jax
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.core import kvcache, model
from repro.core.kvcache import PageAllocator
from repro.core.partition import ShardingPlan, model_layout
from repro.serving.policies import FairScheduler, PriorityScheduler
from repro.serving.prefix_cache import RadixPrefixCache
from repro.serving.router import Router
from repro.serving.scheduler import FCFSScheduler, effective_prompt

PLAN = ShardingPlan(tp=1, kv_cache_dtype="float32")
PSZ = 4


class _Req:
    def __init__(self, rid, prompt, max_new=4, priority=0, client_id=0):
        self.rid, self.prompt, self.max_new_tokens = rid, prompt, max_new
        self.priority, self.client_id = priority, client_id
        self.out_tokens = []


def toks(*ids):
    return np.asarray(ids, np.int32)


# ---------------------------------------------------------------------------
# sharded template: the pool carries a replica dim on the data axes
# ---------------------------------------------------------------------------

def test_paged_template_shards_replicas_over_data_axes():
    cfg = reduced(get_config("tinyllama-42m"), dtype="float32")
    lay = model_layout(cfg, PLAN)
    tmpl = kvcache.paged_cache_template(cfg, PLAN, lay, n_pages=8,
                                        page_size=PSZ, n_replicas=2)
    trips = jax.tree_util.tree_leaves(
        tmpl, is_leaf=lambda x: isinstance(x, tuple) and len(x) == 3
        and isinstance(x[0], tuple))
    assert trips
    for shape, _, spec in trips:
        # (reps, n_replicas, n_pages, G, psz, D), replicas on the dp axes
        assert shape[1] == 2 and shape[2] == 8
        assert tuple(spec)[1] == "data"


def test_fold_replica_pools_roundtrip():
    import jax.numpy as jnp
    pool = jnp.arange(2 * 3 * 4 * 5).reshape(1, 2, 3 * 4 * 5) \
        .reshape(1, 2, 3, 4, 5).astype(jnp.float32)
    folded = kvcache.fold_replica_pools(pool)
    assert folded.shape == (1, 6, 4, 5)
    # replica i's page p lands at folded id i*n_pages + p
    np.testing.assert_array_equal(np.asarray(folded[0, 3 + 2]),
                                  np.asarray(pool[0, 1, 2]))
    back = kvcache.unfold_replica_pools(folded, 2)
    np.testing.assert_array_equal(np.asarray(back), np.asarray(pool))


# ---------------------------------------------------------------------------
# allocator: free() refuses shared pages (satellite bugfix)
# ---------------------------------------------------------------------------

def test_free_refuses_shared_pages():
    a = PageAllocator(8)
    pages = a.alloc(3)
    a.incref(pages)                       # now shared (e.g. prefix cache)
    with pytest.raises(AssertionError, match="decref"):
        a.free(pages)
    assert a.refcount(pages[0]) == 2      # nothing was dropped
    a.decref(pages)                       # the legitimate multi-ref release
    a.free(pages)                         # sole owner: fine
    assert a.n_free == 7


# ---------------------------------------------------------------------------
# router: prefix affinity first, then least page load
# ---------------------------------------------------------------------------

def _mk_replicas(n, n_pages=33, prefix=True, mk_sched=None):
    allocs = [PageAllocator(n_pages) for _ in range(n)]
    caches = [RadixPrefixCache(a, PSZ) if prefix else None for a in allocs]
    mk = mk_sched or (lambda **kw: FCFSScheduler(**kw))
    scheds = [mk(seq_budget=64, allocator=a, page_size=PSZ, prefix_cache=c,
                 stats=None) for a, c in zip(allocs, caches, strict=True)]
    return scheds, allocs, caches


def test_router_prefix_affinity_wins():
    scheds, allocs, caches = _mk_replicas(2)
    router = Router(scheds, allocs, caches, PSZ)
    # replica 1 holds an 8-token prefix; replica 0 is emptier
    pages = allocs[1].alloc(2)
    caches[1].insert(toks(*range(10, 18)), pages)
    allocs[1].decref(pages)               # cache-owned now
    req = _Req(0, toks(*range(10, 18), 99))
    assert router.route(req) == 1         # affinity beats load
    assert router.affinity_routed == 1
    # no affinity anywhere -> least loaded (replica 0: no cached pin,
    # but replica 1's cached pages are evictable so loads tie -> lowest idx)
    assert router.route(_Req(1, toks(7, 7, 7))) == 0


def test_router_least_loaded_counts_backlog_and_pins():
    scheds, allocs, caches = _mk_replicas(2)
    router = Router(scheds, allocs, caches, PSZ)
    # replica 0 gets a queued backlog; no prefix hits anywhere
    big = _Req(0, toks(*range(16)), max_new=8)        # 6 pages of demand
    scheds[0].submit(big)
    assert router.page_load(0) == 6 and router.page_load(1) == 0
    assert router.route(_Req(1, toks(1, 2, 3))) == 1
    # live-slot pins count too: admit on replica 1
    scheds[1].submit(_Req(2, toks(*range(8)), max_new=8))  # 4 pages
    (adm,) = scheds[1].plan([0])
    assert router.page_load(1) == 4
    scheds[1].on_finish(adm)
    assert router.page_load(1) == 0       # released pages drop the load


def test_router_sticky_resume_after_preemption():
    """A preempted request's donation lands in its own replica's cache, so
    re-routing it (hypothetically) would pick the same replica."""
    mk = lambda **kw: PriorityScheduler(preemption=True, **kw)  # noqa: E731
    scheds, allocs, caches = _mk_replicas(2, mk_sched=mk)
    router = Router(scheds, allocs, caches, PSZ)
    req = _Req(0, toks(*range(20, 28)), max_new=8)
    r = router.route(req)
    scheds[r].submit(req)
    (adm,) = scheds[r].plan([0])
    scheds[r].on_prefill_complete(adm)
    req.out_tokens = [1, 2, 3, 4]
    scheds[r].on_preempt(adm, effective_prompt(req)[:12])
    assert router.route(req) == r         # donated pages pull it back home


# ---------------------------------------------------------------------------
# randomized property: conservation + leak-freedom, dp x policy, with
# forced preemption — totals hold PER REPLICA
# ---------------------------------------------------------------------------

def _policies():
    return [
        ("fcfs", lambda **kw: FCFSScheduler(**kw)),
        ("priority", lambda **kw: PriorityScheduler(preemption=True, **kw)),
        ("fair", lambda **kw: FairScheduler(quantum=8, preemption=True,
                                            **kw)),
    ]


@pytest.mark.parametrize("dp", [1, 2])
@pytest.mark.parametrize("name,mk", _policies(),
                         ids=[p[0] for p in _policies()])
def test_dp_policies_conserve_requests_and_pages(name, mk, dp):
    for seed in range(3):
        rng = np.random.RandomState(seed)
        scheds, allocs, caches = _mk_replicas(dp, n_pages=33, mk_sched=mk)
        router = Router(scheds, allocs, caches, PSZ)
        reqs = [_Req(rid, toks(*rng.randint(2, 50, rng.randint(1, 13))),
                     max_new=int(rng.randint(1, 7)),
                     priority=int(rng.randint(0, 4)),
                     client_id=int(rng.randint(0, 3)))
                for rid in range(20)]
        homes = {}
        for r in reqs:
            homes[r.rid] = router.route(r)
            scheds[homes[r.rid]].submit(r)
        # slots are replica-local: 2 per replica
        active = {rr: {} for rr in range(dp)}
        finished, preempts = set(), 0
        for _step in range(5000):
            if len(finished) == len(reqs):
                break
            for rr in range(dp):
                sched, act = scheds[rr], active[rr]
                free = [s for s in range(2) if s not in act]
                for adm in sched.plan(free):
                    if adm.cow is not None:        # engine copies, then:
                        sched.on_cow_done(adm)
                    act[adm.slot] = [adm, False]
                for slot in list(act):
                    adm, prefilled = act[slot]
                    req = adm.req
                    if rng.rand() < 0.15 and preempts < 60:
                        n = (len(req.prompt) + len(req.out_tokens) - 1
                             if prefilled and req.out_tokens else
                             int(rng.randint(0, len(req.prompt) + 1)))
                        sched.on_preempt(adm,
                                         effective_prompt(req)[:max(n, 0)])
                        del act[slot]
                        preempts += 1
                        continue
                    if not prefilled:
                        sched.on_prefill_complete(adm)
                        act[slot][1] = True
                    req.out_tokens.append(int(rng.randint(2, 50)))
                    if len(req.out_tokens) >= req.max_new_tokens:
                        sched.on_finish(adm)
                        finished.add(req.rid)
                        del act[slot]
        assert finished == {r.rid for r in reqs}, (name, dp, seed)
        # leak-freedom per replica: every page free or cache-held, and the
        # router's O(1) backlog counter drained to zero with the queues
        for rr in range(dp):
            assert not scheds[rr].has_pending()
            assert scheds[rr].backlog_pages == 0, (name, dp, seed, rr)
            assert allocs[rr].n_free + caches[rr].n_cached_pages == 32, \
                (name, dp, seed, rr)
            caches[rr].evict(10 ** 6)
            assert allocs[rr].n_free == 32, (name, dp, seed, rr)


# ---------------------------------------------------------------------------
# engine level: dp=2 == dp=1 oracle (token identity, affinity, drain)
# ---------------------------------------------------------------------------

def _mixed_requests(cfg, n=10, seed=0, shared_prefix=0):
    from repro.serving import Request
    rng = np.random.RandomState(seed)
    shared = rng.randint(2, cfg.vocab_size, shared_prefix).astype(np.int32)
    out = []
    for rid in range(n):
        L = int(rng.randint(4, 16))
        p = rng.randint(2, cfg.vocab_size, L).astype(np.int32)
        out.append(Request(rid=rid,
                           prompt=np.concatenate([shared, p]),
                           max_new_tokens=int(rng.randint(2, 7)),
                           priority=int(rng.randint(0, 3)),
                           client_id=rid % 2))
    return out


def _run_engine(cfg, params, mesh1, dp, reqs, scheduler=None,
                prefix_cache=True, max_ticks=5000, plan=PLAN):
    from repro.serving import ServingEngine
    eng = ServingEngine.build_paged(cfg, plan, mesh1, 2, 64, params,
                                    page_size=8, prefill_chunk=16,
                                    prefix_cache=prefix_cache,
                                    scheduler=scheduler, dp=dp)
    for r in reqs:
        eng.submit(r)
    eng.run(max_ticks=max_ticks)
    return eng


@pytest.mark.slow
@pytest.mark.parametrize("mk_sched", [
    None,
    lambda **kw: PriorityScheduler(preemption=True, **kw),
    lambda **kw: FairScheduler(preemption=True, **kw)],
    ids=["fcfs", "priority", "fair"])
def test_dp2_greedy_token_identical_to_dp1_oracle(mesh1, mk_sched):
    cfg = reduced(get_config("tinyllama-42m"), dtype="float32")
    params = model.init_params(cfg, PLAN)
    ref = _mixed_requests(cfg)
    _run_engine(cfg, params, mesh1, 1, ref, scheduler=None)
    assert all(r.done for r in ref)
    got = _mixed_requests(cfg)
    eng = _run_engine(cfg, params, mesh1, 2, got, scheduler=mk_sched)
    assert all(r.done for r in got)
    assert {r.rid: tuple(r.out_tokens) for r in got} == \
           {r.rid: tuple(r.out_tokens) for r in ref}
    assert {r.replica for r in got} == {0, 1}      # both replicas used
    # per-replica leak-freedom after a full run
    for rr in range(2):
        a, c = eng.allocators[rr], eng.prefix_caches[rr]
        assert a.n_free + c.n_cached_pages == a.n_pages - a.n_reserved, rr


@pytest.mark.slow
def test_dp2_prefix_affinity_routes_shared_prefix_together(mesh1):
    """On a shared-system-prompt workload, once one replica owns the
    prefix every later request follows it there (nonzero hit rate), while
    distinct-prefix requests still spread over both replicas."""
    cfg = reduced(get_config("tinyllama-42m"), dtype="float32")
    params = model.init_params(cfg, PLAN)
    reqs = _mixed_requests(cfg, n=8, shared_prefix=16)
    eng = _run_engine(cfg, params, mesh1, 2, reqs)
    assert all(r.done for r in reqs)
    # the first request seeds one replica's cache; everyone else follows
    home = reqs[0].replica
    followers = [r for r in reqs if r.replica == home]
    assert len(followers) >= len(reqs) - 1
    rs = eng.stats.replicas[home]
    assert rs.prefix_hits > 0 and rs.prefix_hit_rate > 0
    assert eng.router.affinity_routed > 0
    assert eng.stats.prefill_tokens_skipped > 0


@pytest.mark.slow
def test_dp2_drain_releases_both_replicas(mesh1):
    from repro.serving import Request, ServingEngine
    cfg = reduced(get_config("tinyllama-42m"), dtype="float32")
    params = model.init_params(cfg, PLAN)
    eng = ServingEngine.build_paged(cfg, PLAN, mesh1, 2, 64, params,
                                    page_size=8, prefill_chunk=16,
                                    prefix_cache=True, dp=2)
    rng = np.random.RandomState(0)
    reqs = [Request(rid=i, prompt=rng.randint(2, cfg.vocab_size,
                                              12).astype(np.int32),
                    max_new_tokens=8) for i in range(6)]
    for r in reqs:
        eng.submit(r)
    eng.run(max_ticks=2)                      # strands work mid-flight
    assert any(a is not None for a in eng.admissions)
    n = eng.drain()
    assert n > 0 and all(a is None for a in eng.admissions)
    for rr in range(2):
        a, c = eng.allocators[rr], eng.prefix_caches[rr]
        assert a.n_free + c.n_cached_pages == a.n_pages - a.n_reserved, rr


def test_dp_requires_paged_and_factory(mesh1):
    import jax
    from repro.configs.base import ShapeConfig
    from repro.core import steps
    from repro.serving import ServingEngine
    cfg = reduced(get_config("tinyllama-42m"), dtype="float32")
    params = model.init_params(cfg, PLAN)
    dshape = ShapeConfig("dp_d", "decode", 32, 2)
    pshape = ShapeConfig("dp_p", "decode", 32, 1)
    dec, _, _ = steps.make_decode_step(cfg, PLAN, mesh1, dshape)
    pre, _, _ = steps.make_prefill_step(cfg, PLAN, mesh1, pshape)
    with pytest.raises(AssertionError, match="paged"):
        ServingEngine(cfg, PLAN, mesh1, 2, 32, params, jax.jit(pre),
                      jax.jit(dec), dp=2)


@pytest.mark.slow
def test_dp2_equivalence_on_real_data_mesh_subprocess():
    """dp=2 on a REAL (data=2, model=1) mesh — each device holding only its
    replica's pages — matches the 1-device dp=1 oracle token for token.
    Runs tests/dp_equiv_main.py under 2 host devices."""
    import os
    import subprocess
    import sys
    root = os.path.join(os.path.dirname(__file__), "..")
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(root, "src")
    r = subprocess.run([sys.executable,
                        os.path.join(root, "tests", "dp_equiv_main.py")],
                       capture_output=True, text=True, env=env,
                       timeout=1200)
    assert "dp-equivalence OK" in r.stdout, \
        r.stdout[-3000:] + r.stderr[-2000:]


def test_n_replicas_must_cover_data_extent():
    from repro.core import steps as _steps

    class _FakeMesh:
        axis_names = ("data", "model")
        devices = np.zeros((2, 1))
    with pytest.raises(AssertionError, match="multiple"):
        _steps.n_replicas_local(_FakeMesh(), PLAN, 3)
    assert _steps.n_replicas_local(_FakeMesh(), PLAN, 4) == 2


# ---------------------------------------------------------------------------
# quantized pools: dp equivalence and scale-tensor hygiene
# ---------------------------------------------------------------------------

PLAN_I8 = ShardingPlan(tp=1, kv_cache_dtype="int8")


def _assert_scale_hygiene(eng):
    """Every free page either awaits its scale reset (``_scale_dirty``) or
    its device scale rows are exactly zero — a recycled page can never pair
    stale scales with fresh payloads."""
    for rr in range(eng.R):
        a = eng.allocators[rr]
        clean = sorted(a._free_set - a._scale_dirty)
        if not clean:
            continue
        idx = np.asarray(clean, np.int32)
        for pat in eng.cache:
            for d in pat:
                for kind in ("kv", "cross"):
                    leaves = d.get(kind)
                    if not isinstance(leaves, dict):
                        continue
                    for kk, vv in leaves.items():
                        if kk.endswith("sp"):
                            rows = np.asarray(vv[:, rr, idx])
                            assert not rows.any(), (rr, kk, clean)


@pytest.mark.slow
def test_dp2_int8_greedy_token_identical_to_fp_oracle(mesh1):
    """int8 pools under dp: per-row quantization is value-deterministic,
    so routing/interleaving differences between dp=1 and dp=2 cannot
    change any page's bytes — greedy outputs match the fp dp=1 oracle."""
    cfg = reduced(get_config("tinyllama-42m"), dtype="float32")
    params = model.init_params(cfg, PLAN)
    ref = _mixed_requests(cfg)
    _run_engine(cfg, params, mesh1, 1, ref)
    assert all(r.done for r in ref)
    want = {r.rid: tuple(r.out_tokens) for r in ref}

    got1 = _mixed_requests(cfg)
    eng1 = _run_engine(cfg, params, mesh1, 1, got1, plan=PLAN_I8)
    assert all(r.done for r in got1)
    assert {r.rid: tuple(r.out_tokens) for r in got1} == want
    _assert_scale_hygiene(eng1)

    got2 = _mixed_requests(cfg)
    eng2 = _run_engine(
        cfg, params, mesh1, 2, got2, plan=PLAN_I8,
        scheduler=lambda **kw: PriorityScheduler(preemption=True, **kw))
    assert all(r.done for r in got2)
    assert {r.rid: tuple(r.out_tokens) for r in got2} == want
    assert {r.replica for r in got2} == {0, 1}
    for rr in range(2):
        a, c = eng2.allocators[rr], eng2.prefix_caches[rr]
        assert a.n_free + c.n_cached_pages == a.n_pages - a.n_reserved, rr
    _assert_scale_hygiene(eng2)


@pytest.mark.slow
def test_dp2_int8_randomized_preemption_scale_hygiene(mesh1):
    """Randomized churn (tight pool, forced preemptions) with int8 pools:
    page conservation holds per replica AND the scale side tensors stay
    hygienic — at every checkpoint each free page is either queued for its
    reset or already zeroed on device."""
    from repro.serving import ServingEngine
    cfg = reduced(get_config("tinyllama-42m"), dtype="float32")
    params = model.init_params(cfg, PLAN)
    eng = ServingEngine.build_paged(
        cfg, PLAN_I8, mesh1, 2, 64, params, page_size=8, prefill_chunk=16,
        n_pages=17, prefix_cache=True, dp=2,
        scheduler=lambda **kw: PriorityScheduler(preemption=True, **kw))
    reqs = _mixed_requests(cfg, n=12, seed=5)
    for r in reqs:
        eng.submit(r)
    rng = np.random.RandomState(7)
    tick = 0
    while (eng.has_pending() or
           any(a is not None for a in eng.admissions)) and tick < 2000:
        if tick % 7 == 3:                       # forced preemption churn
            occ = [b for b in range(eng.B) if eng.admissions[b] is not None]
            if occ:
                eng.preempt(int(rng.choice(occ)))
        eng.tick()
        tick += 1
        if tick % 25 == 0:
            _assert_scale_hygiene(eng)
    assert all(r.done for r in reqs)
    for rr in range(2):
        a, c = eng.allocators[rr], eng.prefix_caches[rr]
        assert a.n_free + c.n_cached_pages == a.n_pages - a.n_reserved, rr
    _assert_scale_hygiene(eng)
