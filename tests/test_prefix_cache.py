"""Prefix-cache subsystem: radix tree hit/miss/partial-hit, allocator
refcounts, LRU eviction, the COW page-copy step, and engine-level
equivalence — greedy outputs with the prefix cache on are token-identical
to the cache-off oracle while allocating measurably fewer pages."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.core import model, steps
from repro.core.kvcache import PageAllocator
from repro.core.partition import ShardingPlan
from repro.serving.prefix_cache import RadixPrefixCache
from repro.serving.scheduler import FCFSScheduler

PLAN = ShardingPlan(tp=1, kv_cache_dtype="float32")
PSZ = 4


def toks(*ids):
    return np.asarray(ids, np.int32)


# ---------------------------------------------------------------------------
# allocator refcounts
# ---------------------------------------------------------------------------

def test_allocator_refcounts():
    a = PageAllocator(8)
    pages = a.alloc(3)
    assert [a.refcount(p) for p in pages] == [1, 1, 1]
    a.incref(pages)                       # a second owner (e.g. the cache)
    a.decref(pages)
    assert a.n_free == 4                  # still alive: one ref remains
    assert all(a.refcount(p) == 1 for p in pages)
    a.decref(pages)
    assert a.n_free == 7                  # last ref dropped -> pool
    with pytest.raises(AssertionError):
        a.incref([pages[0]])              # can't share a freed page
    assert a.total_allocated == 3


# ---------------------------------------------------------------------------
# radix tree: hit / miss / partial hit / split / refcounts
# ---------------------------------------------------------------------------

def _cache(n_pages=32):
    a = PageAllocator(n_pages)
    return a, RadixPrefixCache(a, PSZ)


def test_radix_miss_and_exact_hit():
    a, c = _cache()
    assert c.lookup(toks(1, 2, 3, 4)) == (0, [])
    pages = a.alloc(2)
    c.insert(toks(1, 2, 3, 4, 5, 6, 7, 8), pages)
    assert all(a.refcount(p) == 2 for p in pages)  # slot ref + cache ref
    m, run = c.lookup(toks(1, 2, 3, 4, 5, 6, 7, 8))
    assert m == 8 and run == pages
    # shorter aligned prefix
    m, run = c.lookup(toks(1, 2, 3, 4))
    assert m == 4 and run == pages[:1]
    # unrelated prompt
    assert c.lookup(toks(9, 9, 9, 9))[0] == 0


def test_radix_partial_hit_mid_page_is_cow_source():
    a, c = _cache()
    pages = a.alloc(2)
    c.insert(toks(1, 2, 3, 4, 5, 6, 7, 8), pages)
    # diverges inside the first page: match_len 2, page 0 is the COW source
    m, run = c.lookup(toks(1, 2, 99, 98, 97))
    assert m == 2 and run == [pages[0]]
    # diverges inside the second page
    m, run = c.lookup(toks(1, 2, 3, 4, 5, 99))
    assert m == 5 and run == pages


def test_radix_split_shares_page_aligned_prefix():
    a, c = _cache()
    p1 = a.alloc(2)
    c.insert(toks(1, 2, 3, 4, 5, 6, 7, 8), p1)
    p2 = a.alloc(2)
    # same first page of tokens, different second page
    new = c.insert(toks(1, 2, 3, 4, 50, 60, 70, 80), p2)
    assert new == 1                       # only the divergent page is new
    assert a.refcount(p2[0]) == 1         # duplicate first page NOT cached
    assert a.refcount(p2[1]) == 2
    m, run = c.lookup(toks(1, 2, 3, 4, 50, 60, 70, 80))
    assert m == 8 and run == [p1[0], p2[1]]   # shared structural prefix
    m, run = c.lookup(toks(1, 2, 3, 4, 5, 6, 7, 8))
    assert m == 8 and run == p1
    assert c.n_nodes == 3                 # split parent + two tails


def test_radix_lru_eviction_and_shared_protection():
    a, c = _cache(n_pages=32)
    p1, p2 = a.alloc(1), a.alloc(1)
    c.insert(toks(1, 2, 3, 4), p1)
    c.insert(toks(9, 8, 7, 6), p2)
    a.decref(p1)                          # both runs now cache-only...
    c.lookup(toks(1, 2, 3, 4))            # ...but run 1 is recently used
    # run 2 still carries its slot ref: eviction must skip it
    freed = c.evict(1)
    assert freed == 1                     # evicted run 1 (LRU among free)
    assert c.lookup(toks(1, 2, 3, 4))[0] == 0
    assert c.lookup(toks(9, 8, 7, 6))[0] == 4
    a.decref(p2)
    freed = c.evict(5)                    # more than cached: frees what it can
    assert freed == 1 and c.n_nodes == 0
    assert a.n_free == 31


def test_radix_eviction_children_before_parents():
    a, c = _cache()
    p = a.alloc(3)
    c.insert(toks(1, 2, 3, 4, 5, 6, 7, 8), p[:2])
    c.insert(toks(1, 2, 3, 4, 50, 60, 70, 80), [p[0], p[2]])
    a.decref(p)                           # cache is now the sole owner
    assert c.evict(3) == 3                # leaves first, then the parent
    assert c.n_nodes == 0 and a.n_free == 31


# ---------------------------------------------------------------------------
# COW page-copy step
# ---------------------------------------------------------------------------

def test_page_copy_step(mesh1):
    cfg = reduced(get_config("qwen3-0.6b"), dtype="float32")
    N_PAGES, P_SZ = 6, 4
    copy_fn, _, _ = steps.make_page_copy_step(cfg, PLAN, mesh1, N_PAGES, P_SZ)
    copy_fn = jax.jit(copy_fn)
    cache = steps.zero_paged_cache_for(cfg, PLAN, mesh1, N_PAGES, P_SZ)
    rng = np.random.RandomState(0)
    cache = jax.tree_util.tree_map(
        lambda x: jnp.asarray(rng.randn(*x.shape), x.dtype), cache)
    with mesh1:
        out = copy_fn(cache, jnp.asarray([2], jnp.int32),
                      jnp.asarray([5], jnp.int32))
    for old, new in zip(jax.tree_util.tree_leaves(cache),
                        jax.tree_util.tree_leaves(out), strict=True):
        # pools carry a leading replica dim: (reps, R, n_pages, psz, G, D)
        old, new = np.asarray(old)[:, 0], np.asarray(new)[:, 0]
        np.testing.assert_array_equal(new[:, 5], old[:, 2])     # copied
        keep = [i for i in range(N_PAGES) if i != 5]
        np.testing.assert_array_equal(new[:, keep], old[:, keep])


# ---------------------------------------------------------------------------
# scheduler-level: COW planning against a tight pool
# ---------------------------------------------------------------------------

class _Req:
    def __init__(self, rid, prompt, max_new):
        self.rid, self.prompt, self.max_new_tokens = rid, prompt, max_new


def test_scheduler_plans_cow_and_rolls_back_under_pressure():
    a = PageAllocator(8)                  # 7 usable
    c = RadixPrefixCache(a, PSZ)
    stats = None
    sched = FCFSScheduler(seq_budget=64, allocator=a, page_size=PSZ,
                          prefix_cache=c, stats=stats)
    seed_pages = a.alloc(2)
    c.insert(toks(1, 2, 3, 4, 5, 6, 7, 8), seed_pages)
    a.decref(seed_pages)                  # cache-only now (5 free)
    # partial hit: 6 of 8 tokens -> 1 shared page + COW copy of page 2
    sched.submit(_Req(0, toks(1, 2, 3, 4, 5, 6, 90, 91), 4))
    (adm,) = sched.plan([0])
    assert adm.cached_len == 6
    assert adm.pages[0] == seed_pages[0]
    assert adm.cow == (seed_pages[1], adm.pages[1])
    assert a.refcount(seed_pages[0]) == 2      # shared full page pinned
    assert a.refcount(seed_pages[1]) == 2      # COW source pinned
    sched.on_cow_done(adm)
    assert a.refcount(seed_pages[1]) == 1      # pin released after the copy
    # a request too big for the remaining pool: head-of-line blocks cleanly
    # (needs 6 pages; only 3 free and the cached run is pinned by adm)
    sched.submit(_Req(1, toks(*range(40, 60)), 4))
    assert sched.plan([1]) == []
    assert a.refcount(seed_pages[0]) == 2      # rollback left refs intact
    sched.on_finish(adm)
    assert a.refcount(seed_pages[0]) == 1
    # retirement freed slot pages; eviction reclaims the now-unpinned run
    (adm2,) = sched.plan([1])
    assert adm2.req.rid == 1 and len(adm2.pages) == 6
    assert c.n_nodes == 0                      # evicted under pressure


def test_scheduler_skips_futile_eviction_and_keeps_hot_prefixes():
    """When eviction cannot cover the shortfall anyway, blocking must not
    wipe cached runs — queued requests would lose the hot prefix for
    nothing."""
    a = PageAllocator(11)                 # 10 usable
    c = RadixPrefixCache(a, PSZ)
    sched = FCFSScheduler(seq_budget=64, allocator=a, page_size=PSZ,
                          prefix_cache=c, stats=None)
    slot_held = a.alloc(6)                # in-flight slots elsewhere
    run = a.alloc(2)
    c.insert(toks(1, 2, 3, 4, 5, 6, 7, 8), run)
    a.decref(run)                         # hot cached run; 2 pages free
    sched.submit(_Req(0, toks(*range(20, 38)), 2))   # needs 5, no match
    assert sched.plan([0]) == []          # blocks...
    assert c.n_nodes == 1                 # ...without wiping the hot run
    a.decref(slot_held)                   # slots retire
    (adm,) = sched.plan([0])
    assert len(adm.pages) == 5
    assert c.n_nodes == 1                 # still cached: free pages sufficed
    sched.on_finish(adm)


def test_scheduler_degrades_to_cold_prefill_instead_of_livelock():
    """A submit-accepted request must never block forever on its own prefix
    pins: when the matched run is unevictable only because the request
    pinned it, admission falls back to a cold prefill."""
    a = PageAllocator(8)                  # 7 usable
    c = RadixPrefixCache(a, PSZ)
    sched = FCFSScheduler(seq_budget=64, allocator=a, page_size=PSZ,
                          prefix_cache=c, stats=None)
    run = a.alloc(2)
    c.insert(toks(1, 2, 3, 4, 5, 6, 7, 8), run)
    a.decref(run)                         # cache-only (5 free)
    # shares 6/8 tokens; needs all 7 usable pages -> prefix pins would
    # leave only 5 free with 6 needed and nothing evictable
    sched.submit(_Req(0, toks(1, 2, 3, 4, 5, 6, 90, 91, 92, 93, 94, 95,
                              96, 97, 98, 99, 100, 101, 102, 103, 104), 7))
    (adm,) = sched.plan([0])              # pre-fix: [] forever (livelock)
    assert adm.cached_len == 0 and adm.cow is None
    assert len(adm.pages) == 7            # cold: full budget, run evicted
    assert c.n_nodes == 0
    sched.on_finish(adm)
    assert a.n_free == 7


def test_scheduler_rejects_over_budget_request():
    sched = FCFSScheduler(seq_budget=16, allocator=PageAllocator(8),
                          page_size=PSZ)
    with pytest.raises(RuntimeError, match="budget"):
        sched.submit(_Req(0, toks(*range(16)), 4))    # 16 + 4 > 16
    sched.submit(_Req(1, toks(*range(12)), 4))        # exactly 16: fine


def test_scheduler_rejects_empty_prompt():
    sched = FCFSScheduler(seq_budget=16, allocator=PageAllocator(8),
                          page_size=PSZ, prefix_cache=None, stats=None)
    with pytest.raises(RuntimeError, match="empty"):
        sched.submit(_Req(0, toks(), 4))


# ---------------------------------------------------------------------------
# engine-level: shared-prefix workload equivalence + fewer pages
# ---------------------------------------------------------------------------

def _mk_requests(cfg, seed=0):
    from repro.serving import Request
    rng = np.random.RandomState(seed)
    base = rng.randint(2, cfg.vocab_size, 21).astype(np.int32)
    prompts = []
    for i in range(5):                    # shared 21-token system prompt
        suf = rng.randint(2, cfg.vocab_size, 3 + i).astype(np.int32)
        prompts.append(np.concatenate([base, suf]).astype(np.int32))
    # diverges mid-page (shares 5 of the first 8 tokens): exercises COW
    prompts.append(np.concatenate(
        [base[:5], rng.randint(2, cfg.vocab_size, 6).astype(np.int32)]))
    # identical full prompt, length a page multiple: COW via the >=1-token
    # prefill floor (cached_len capped at L-1)
    prompts.append(prompts[0].copy())
    return [Request(rid=i, prompt=p.astype(np.int32), max_new_tokens=5)
            for i, p in enumerate(prompts)]


def _run_engine(cfg, params, mesh1, prefix_cache, n_pages=0):
    from repro.serving import ServingEngine
    eng = ServingEngine.build_paged(cfg, PLAN, mesh1, 2, 64, params,
                                    page_size=8, prefill_chunk=16,
                                    n_pages=n_pages,
                                    prefix_cache=prefix_cache)
    reqs = _mk_requests(cfg)
    for r in reqs:
        eng.submit(r)
    stats = eng.run(max_ticks=10_000)
    return eng, reqs, stats


@pytest.mark.slow
def test_prefix_cache_engine_matches_oracle_and_saves_pages(mesh1):
    cfg = reduced(get_config("tinyllama-42m"), dtype="float32")
    params = model.init_params(cfg, PLAN)
    e_off, r_off, s_off = _run_engine(cfg, params, mesh1, prefix_cache=False)
    e_on, r_on, s_on = _run_engine(cfg, params, mesh1, prefix_cache=True)
    for a, b in zip(r_off, r_on, strict=True):
        assert a.done and b.done
        assert a.out_tokens == b.out_tokens, a.rid   # greedy token-identical
    # the shared prefix was actually reused, including COW divergences
    assert s_on.prefill_tokens_skipped > 0
    assert s_on.cow_copies >= 2           # mid-page diverger + resubmission
    assert s_on.prefix_hits > 0 and s_on.prefix_hit_rate > 0
    assert s_off.prefill_tokens_skipped == 0
    # measurably fewer pages pulled from the pool
    assert e_on.allocator.total_allocated < e_off.allocator.total_allocated
    # accounting: every non-cached page returned; cache refs balance
    usable = e_on.allocator.n_pages - e_on.allocator.n_reserved
    assert e_on.allocator.n_free + e_on.prefix_cache.n_cached_pages == usable
    assert e_off.allocator.n_free == \
        e_off.allocator.n_pages - e_off.allocator.n_reserved
    # per-request TTFT recorded for every request
    assert set(s_on.request_ttft) == {r.rid for r in r_on}


@pytest.mark.slow
def test_prefix_cache_evicts_under_pool_exhaustion(mesh1):
    """Distinct prompts through a pool that can't hold them all cached:
    LRU eviction keeps admissions flowing and every request completes."""
    from repro.serving import Request, ServingEngine
    cfg = reduced(get_config("tinyllama-42m"), dtype="float32")
    params = model.init_params(cfg, PLAN)
    eng = ServingEngine.build_paged(cfg, PLAN, mesh1, 2, 32, params,
                                    page_size=8, prefill_chunk=8,
                                    n_pages=9, prefix_cache=True)  # 8 usable
    rng = np.random.RandomState(3)
    reqs = []
    for rid in range(12):
        L = int(rng.randint(8, 20))
        req = Request(rid=rid,
                      prompt=rng.randint(2, cfg.vocab_size, L).astype(np.int32),
                      max_new_tokens=int(rng.randint(1, 6)))
        reqs.append(req)
        eng.submit(req)
    eng.run(max_ticks=20_000)
    assert all(r.done for r in reqs)
    assert eng.prefix_cache.evictions > 0          # pressure really happened
    usable = eng.allocator.n_pages - eng.allocator.n_reserved
    assert eng.allocator.n_free + eng.prefix_cache.n_cached_pages == usable
