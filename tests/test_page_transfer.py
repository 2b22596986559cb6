"""The page-transfer primitive and prefill/decode disaggregation:
``kvcache.handoff_refs`` refcount handoff (source decref exactly once,
destination freshly owned), ``core.steps.make_page_transfer_step``
byte-identity for int8 payloads + scale rows, forced preemption of a slot
queued for handoff, and the dp=2 disaggregated engine's token identity
against the dp=1 serial oracle."""
import jax
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.core import kvcache, model
from repro.core import steps as _steps
from repro.core.kvcache import PageAllocator, handoff_refs
from repro.core.partition import ShardingPlan

PLAN = ShardingPlan(tp=1, kv_cache_dtype="float32")
PLAN_I8 = ShardingPlan(tp=1, kv_cache_dtype="int8")


# ---------------------------------------------------------------------------
# refcount handoff
# ---------------------------------------------------------------------------

def test_handoff_refs_decrefs_source_once():
    src, dst = PageAllocator(8), PageAllocator(8)
    pages = src.alloc(3)
    src.incref(pages[:2])              # first two shared (prefix cache)
    fresh = dst.alloc(3)
    handoff_refs(src, pages, dst, fresh)
    # source dropped exactly ONE ref per page: shared pages stay resident
    # for the cache, the private tail page frees
    assert src.refcount(pages[0]) == 1
    assert src.refcount(pages[1]) == 1
    assert src.refcount(pages[2]) == 0
    assert src.n_free == 8 - 1 - 2     # scratch reserved + 2 cache-held
    # destination ownership is exactly the fresh allocation
    assert all(dst.refcount(p) == 1 for p in fresh)
    assert src.pages_transferred_out == 3
    assert dst.pages_transferred_in == 3
    dst.decref(fresh)
    assert dst.n_free == 8 - 1


def test_handoff_refs_rejects_shared_destination():
    src, dst = PageAllocator(8), PageAllocator(8)
    pages = src.alloc(2)
    shared = dst.alloc(2)
    dst.incref(shared)                 # destination pages NOT freshly owned
    with pytest.raises(AssertionError, match="freshly allocated"):
        handoff_refs(src, pages, dst, shared)
    # nothing moved: the source still owns its run
    assert all(src.refcount(p) == 1 for p in pages)
    assert src.pages_transferred_out == 0


def test_handoff_refs_rejects_same_allocator_and_length_mismatch():
    a, b = PageAllocator(8), PageAllocator(8)
    pages = a.alloc(2)
    with pytest.raises(AssertionError, match="within one replica"):
        handoff_refs(a, pages, a, pages)
    with pytest.raises(AssertionError):
        handoff_refs(a, pages, b, b.alloc(1))


# ---------------------------------------------------------------------------
# transfer step: int8 payload + scale rows move byte-identically
# ---------------------------------------------------------------------------

def _kv_leaves(cache):
    out = []
    for pat in cache:
        for d in pat:
            if "kv" in d:
                out.extend(jax.tree_util.tree_leaves(d["kv"]))
    return out


def _rows(v, pids, n_pages):
    """Rows of leaf ``v``'s page axis holding pages ``pids``: a flat scale
    pool spans page_size rows a page."""
    return np.asarray(kvcache.page_rows(np.asarray(pids, np.int32),
                                        v.shape[2] // n_pages))


def _fill_kv(cache, rep, pids, rng, n_pages):
    """Write deterministic random values into replica ``rep``'s pages
    ``pids`` on every self-KV leaf (payload and scale tensors alike)."""
    def leaf(v):
        if v.ndim < 3:
            return v
        rows = _rows(v, pids, n_pages)
        fill = rng.randint(-127, 128, (v.shape[0], len(rows))
                           + v.shape[3:]).astype(v.dtype)
        return v.at[:, rep, rows].set(fill)

    return [[{k: (jax.tree_util.tree_map(leaf, sub) if k == "kv" else sub)
              for k, sub in d.items()} for d in pat] for pat in cache]


@pytest.mark.parametrize("plan", [PLAN, PLAN_I8], ids=["fp32", "int8"])
def test_transfer_step_moves_payload_and_scales_byte_identical(mesh1, plan):
    cfg = reduced(get_config("tinyllama-42m"), dtype="float32")
    n_pages, psz, lanes = 6, 4, 2
    fn, _, _ = _steps.make_page_transfer_step(cfg, plan, mesh1, n_pages,
                                              psz, lanes, n_replicas=2)
    cache = _steps.zero_paged_cache_for(cfg, plan, mesh1, n_pages, psz,
                                        n_replicas=2)
    rng = np.random.RandomState(0)
    src_pages, dst_pages = [2, 4], [1, 3]
    bystander = 5
    cache = _fill_kv(cache, 0, src_pages + [bystander], rng, n_pages)
    before = [np.asarray(v) for v in _kv_leaves(cache)]
    with mesh1:
        out = fn(cache, np.int32(0), np.int32(1),
                 np.asarray(src_pages, np.int32),
                 np.asarray(dst_pages, np.int32))
    after = [np.asarray(v) for v in _kv_leaves(out)]
    quantized = kvcache.kv_pool_is_quantized(plan)
    assert quantized == any(v.dtype == np.int8 for v in after)
    for b4, af in zip(before, after):
        if b4.ndim < 3:
            continue
        # destination replica's pages carry the exact source bytes —
        # int8 payloads and float32 scale rows never round-trip through
        # a dequantize/requantize
        def rows(p):
            return _rows(b4, [p], n_pages)

        for sp, dp in zip(src_pages, dst_pages):
            np.testing.assert_array_equal(af[:, 1, rows(dp)],
                                          b4[:, 0, rows(sp)])
        # the source pages and untouched pages are bitwise unchanged
        for p in src_pages + [bystander]:
            np.testing.assert_array_equal(af[:, 0, rows(p)],
                                          b4[:, 0, rows(p)])
        np.testing.assert_array_equal(af[:, 1, rows(bystander)],
                                      b4[:, 1, rows(bystander)])


# ---------------------------------------------------------------------------
# engine level: disaggregated serving
# ---------------------------------------------------------------------------

def _requests(cfg, n=8, seed=0, max_new=(2, 7)):
    from repro.serving import Request
    rng = np.random.RandomState(seed)
    return [Request(rid=rid,
                    prompt=rng.randint(2, cfg.vocab_size,
                                       int(rng.randint(4, 20)))
                    .astype(np.int32),
                    max_new_tokens=int(rng.randint(*max_new)))
            for rid in range(n)]


def _run(cfg, params, mesh1, reqs, max_ticks=5000, **kw):
    from repro.serving import ServingEngine
    eng = ServingEngine.build_paged(cfg, PLAN, mesh1, 2, 64, params,
                                    page_size=8, prefill_chunk=16,
                                    prefix_cache=True, **kw)
    for r in reqs:
        eng.submit(r)
    eng.run(max_ticks=max_ticks)
    return eng


def _assert_leak_free(eng):
    for rr in range(eng.R):
        a, c = eng.allocators[rr], eng.prefix_caches[rr]
        cached = c.n_cached_pages if c is not None else 0
        assert a.n_free + cached == a.n_pages - a.n_reserved, rr


@pytest.mark.slow
def test_disagg_dp2_matches_serial_dp1_greedy(mesh1):
    cfg = reduced(get_config("tinyllama-42m"), dtype="float32")
    params = model.init_params(cfg, PLAN)
    ref = _requests(cfg)
    _run(cfg, params, mesh1, ref, dp=1, overlap=False)
    assert all(r.done for r in ref)
    got = _requests(cfg)
    eng = _run(cfg, params, mesh1, got, dp=2, disagg=(1, 1))
    assert all(r.done for r in got)
    assert {r.rid: tuple(r.out_tokens) for r in got} == \
           {r.rid: tuple(r.out_tokens) for r in ref}
    # every request prefilled on replica 0 and finished on replica 1
    assert all(r.replica == 1 for r in got)
    assert eng.stats.handoffs == len(got)
    assert eng.stats.pages_transferred > 0
    r0, r1 = eng.stats.replicas
    assert (r0.role, r1.role) == ("prefill", "decode")
    assert r0.handoffs_out == len(got) and r1.handoffs_in == len(got)
    assert r0.pages_transferred_out == r1.pages_transferred_in \
        == eng.stats.pages_transferred
    assert r0.routed == len(got) and r1.routed == 0
    _assert_leak_free(eng)


@pytest.mark.slow
def test_handoff_preemption_mid_transfer(mesh1):
    """A slot preempted while queued for handoff (after its first token,
    before the transfer dispatched) must roll back cleanly: the request
    re-prefills via the donated-prefix path, hands off later, and both
    replicas stay leak-free with outputs identical to the undisturbed
    run."""
    from repro.serving import ServingEngine
    cfg = reduced(get_config("tinyllama-42m"), dtype="float32")
    params = model.init_params(cfg, PLAN)
    ref = _requests(cfg, n=2, seed=3, max_new=(8, 9))
    _run(cfg, params, mesh1, ref, dp=1, overlap=False)

    reqs = _requests(cfg, n=2, seed=3, max_new=(8, 9))
    eng = ServingEngine.build_paged(cfg, PLAN, mesh1, 1, 64, params,
                                    page_size=8, prefill_chunk=16,
                                    prefix_cache=True, dp=2, disagg=(1, 1))
    for r in reqs:
        eng.submit(r)
    # drive until request 1 sits in the handoff queue (request 0 holds the
    # single decode slot, so the handoff cannot be placed)
    for _ in range(200):
        if eng._pending_handoffs:
            break
        eng.tick()
    assert eng._pending_handoffs, "no slot ever queued for handoff"
    b = eng._pending_handoffs[0]
    victim = eng.admissions[b].req
    assert victim.out_tokens, "handoff queued before the first token"
    eng.preempt(b)
    assert b not in eng._pending_handoffs
    assert eng.admissions[b] is None
    assert eng.stats.preemptions == 1
    eng.run(max_ticks=5000)
    assert all(r.done for r in reqs)
    assert {r.rid: tuple(r.out_tokens) for r in reqs} == \
           {r.rid: tuple(r.out_tokens) for r in ref}
    # the victim was evicted BEFORE its transfer dispatched, so no pages
    # ever moved for the aborted attempt — exactly one executed handoff
    # per request, the victim's coming from its re-prefill
    assert eng.stats.handoffs == len(reqs)
    assert eng.stats.replicas[0].preemptions == 1
    _assert_leak_free(eng)


@pytest.mark.slow
def test_disagg_with_speculation_and_sampling_matches_oracle(mesh1):
    from repro.serving.sampler import SamplerConfig
    cfg = reduced(get_config("tinyllama-42m"), dtype="float32")
    params = model.init_params(cfg, PLAN)
    samp = SamplerConfig(temperature=0.8, top_k=40)
    ref = _requests(cfg, seed=1)
    _run(cfg, params, mesh1, ref, dp=1, overlap=False, sampler=samp,
         rng_seed=7)
    got = _requests(cfg, seed=1)
    eng = _run(cfg, params, mesh1, got, dp=2, disagg=(1, 1), sampler=samp,
               rng_seed=7, speculative=4)
    assert all(r.done for r in got)
    assert {r.rid: tuple(r.out_tokens) for r in got} == \
           {r.rid: tuple(r.out_tokens) for r in ref}
    _assert_leak_free(eng)


def test_disagg_validation(mesh1):
    from repro.serving import ServingEngine
    cfg = reduced(get_config("tinyllama-42m"), dtype="float32")
    params = model.init_params(cfg, PLAN)
    with pytest.raises(ValueError, match="P \\+ D == dp"):
        ServingEngine.build_paged(cfg, PLAN, mesh1, 2, 64, params,
                                  page_size=8, prefill_chunk=16, dp=2,
                                  disagg=(2, 1))
    with pytest.raises(ValueError, match="P \\+ D == dp"):
        ServingEngine.build_paged(cfg, PLAN, mesh1, 2, 64, params,
                                  page_size=8, prefill_chunk=16, dp=2,
                                  disagg=(2, 0))
