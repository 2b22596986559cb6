"""Paged serving beyond attention-only decoders: SSM state slabs
(hybrid + pure-SSM archs), enc-dec cross-KV paging with shared-frame
reuse, preemption snapshot/restore for recurrent state, joint
page+slab+cross leak-freedom across policies and dp, and the precise
errors for unsupported combinations."""
import functools

import numpy as np
import pytest
from greedy_ref import greedy_reference

from repro.configs import get_config, reduced
from repro.core import model
from repro.core.partition import ShardingPlan
from repro.serving import (FairScheduler, PriorityScheduler, Request,
                           ServingEngine)

PLAN = ShardingPlan(tp=1, kv_cache_dtype="float32")


def _hybrid_cfg():
    return reduced(get_config("hymba-1.5b"), dtype="float32")


def _ssm_cfg():
    return reduced(get_config("mamba2-370m"), dtype="float32")


def _encdec_cfg():
    return reduced(get_config("seamless-m4t-large-v2"), dtype="float32",
                   n_enc_layers=1, enc_seq_len=16)


def _mk_requests(base):
    return [Request(rid=i, prompt=p.copy(), max_new_tokens=m, frames=f)
            for i, (p, m, f) in enumerate(base)]


def _assert_leak_free(eng):
    """Every page free or cache-held, every slab free, per replica."""
    for rr in range(eng.R):
        a = eng.allocators[rr]
        cached = 0
        if eng.prefix_caches[rr] is not None:
            cached += eng.prefix_caches[rr].n_cached_pages
        if eng.cross_caches:
            cached += eng.cross_caches[rr].n_cached_pages
        assert a.n_free + cached == a.n_pages - a.n_reserved, rr
        if eng.slab_allocators:
            assert eng.slab_allocators[rr].n_free == eng.n_slabs - 1, rr


# ---------------------------------------------------------------------------
# paged engine vs the contiguous steps' greedy reference (token identity)
# ---------------------------------------------------------------------------

@pytest.mark.slow
@pytest.mark.parametrize("name", ["hymba-1.5b", "mamba2-370m"])
def test_paged_ssm_archs_match_contiguous(name, mesh1):
    """Hybrid (attn KV pages + SSM slabs) and pure-SSM (slabs only) paged
    engines produce greedy outputs token-identical to the contiguous
    steps' greedy reference, and release every page and slab."""
    cfg = reduced(get_config(name), dtype="float32")
    params = model.init_params(cfg, PLAN)
    rng = np.random.RandomState(0)
    base = [(rng.randint(2, cfg.vocab_size, L).astype(np.int32), m, None)
            for L, m in zip([5, 9, 17, 12], [6, 4, 5, 3], strict=True)]
    ref = greedy_reference(cfg, PLAN, mesh1, params, _mk_requests(base), 32)

    eng = ServingEngine.build_paged(cfg, PLAN, mesh1, 2, 32, params,
                                    page_size=8, prefill_chunk=8)
    reqs = _mk_requests(base)
    for r in reqs:
        eng.submit(r)
    eng.run(max_ticks=2000)
    assert all(r.done for r in reqs)
    assert {r.rid: tuple(r.out_tokens) for r in reqs} == ref
    _assert_leak_free(eng)


@pytest.mark.slow
def test_paged_encdec_matches_contiguous_with_shared_frames(mesh1):
    """Enc-dec: cross-KV paged through the second block table; requests
    with identical frames share one encode's pages by refcount."""
    cfg = _encdec_cfg()
    params = model.init_params(cfg, PLAN)
    rng = np.random.RandomState(1)
    frames = [rng.randn(cfg.enc_seq_len, cfg.d_model).astype(np.float32)
              for _ in range(2)]
    base = [(rng.randint(2, cfg.vocab_size, L).astype(np.int32), m,
             frames[i % 2])
            for i, (L, m) in enumerate(zip([5, 9, 12, 7], [5, 4, 3, 6], strict=True))]
    ref = greedy_reference(cfg, PLAN, mesh1, params, _mk_requests(base), 32)

    eng = ServingEngine.build_paged(cfg, PLAN, mesh1, 2, 32, params,
                                    page_size=8, prefill_chunk=8)
    reqs = _mk_requests(base)
    for r in reqs:
        eng.submit(r)
    stats = eng.run(max_ticks=2000)
    assert all(r.done for r in reqs)
    assert {r.rid: tuple(r.out_tokens) for r in reqs} == ref
    # two distinct frame tensors -> exactly two encodes; the rest hit
    assert stats.cross_encodes == 2
    assert stats.cross_hits == 2 and stats.cross_lookups == 4
    _assert_leak_free(eng)
    # the shared cross entries stay resident for future identical frames
    assert eng.cross_caches[0].n_entries == 2


def test_pure_ssm_needs_no_kv_pages(mesh1):
    """A pure-SSM arch has no KV pools, so its per-token page demand is
    zero: requests of any length serve through a minimal page pool and
    the allocator never hands out a page."""
    cfg = _ssm_cfg()
    params = model.init_params(cfg, PLAN)
    eng = ServingEngine.build_paged(cfg, PLAN, mesh1, 2, 32, params,
                                    page_size=8, prefill_chunk=8, n_pages=2)
    rng = np.random.RandomState(0)
    reqs = [Request(rid=i,
                    prompt=rng.randint(2, cfg.vocab_size, L).astype(np.int32),
                    max_new_tokens=4)
            for i, L in enumerate([17, 9, 21])]
    for r in reqs:
        eng.submit(r)
    eng.run(max_ticks=500)
    assert all(r.done for r in reqs)
    assert eng.allocators[0].total_allocated == 0
    _assert_leak_free(eng)


@pytest.mark.slow
def test_dp2_encdec_frames_affinity_shares_encodes(mesh1):
    """dp=2 routing scores a frames-digest hit as affinity, so
    identical-frame requests land on the replica whose encode is already
    resident — one encode per distinct frames, not per replica."""
    cfg = _encdec_cfg()
    params = model.init_params(cfg, PLAN)
    rng = np.random.RandomState(2)
    frames = [rng.randn(cfg.enc_seq_len, cfg.d_model).astype(np.float32)
              for _ in range(2)]
    eng = ServingEngine.build_paged(cfg, PLAN, mesh1, 2, 32, params,
                                    page_size=8, prefill_chunk=8, dp=2)
    reqs = [Request(rid=i,
                    prompt=rng.randint(2, cfg.vocab_size, 8).astype(np.int32),
                    max_new_tokens=3, frames=frames[i % 2])
            for i in range(8)]
    for r in reqs:
        eng.submit(r)
    stats = eng.run(max_ticks=500)
    assert all(r.done for r in reqs)
    assert stats.cross_encodes == 2
    assert stats.cross_hits == 6
    _assert_leak_free(eng)


# ---------------------------------------------------------------------------
# preemption: recurrent state snapshot/restore
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_hybrid_forced_preemption_identity(mesh1):
    """Forced preemption at arbitrary points (mid-prefill and mid-decode)
    leaves hybrid greedy outputs token-identical: the slab checkpoint is
    restored exactly, nothing resident is recomputed wrongly."""
    cfg = _hybrid_cfg()
    params = model.init_params(cfg, PLAN)
    rng = np.random.RandomState(3)
    base = [(rng.randint(2, cfg.vocab_size, L).astype(np.int32), m, None)
            for L, m in zip([13, 9], [8, 6], strict=True)]

    def run(preempt_at):
        eng = ServingEngine.build_paged(cfg, PLAN, mesh1, 2, 32, params,
                                        page_size=8, prefill_chunk=8)
        reqs = _mk_requests(base)
        for r in reqs:
            eng.submit(r)
        tick = 0
        while (eng.has_pending() or
               any(a is not None for a in eng.admissions)) and tick < 500:
            if tick in preempt_at:
                for b in range(eng.B):
                    if eng.admissions[b] is not None:
                        eng.preempt(b)
                        break
            eng.tick()
            tick += 1
        assert all(r.done for r in reqs)
        return {r.rid: tuple(r.out_tokens) for r in reqs}, eng

    ref, _ = run(set())
    for pts in ({1}, {3}, {1, 2, 3}):
        got, eng = run(pts)
        assert got == ref, pts
        assert eng.stats.slab_restores == len(pts)
        _assert_leak_free(eng)


@pytest.mark.slow
def test_encdec_preemption_reencodes_or_hits(mesh1):
    """Enc-dec preemption releases the slot's cross ref; resume re-acquires
    the shared entry (no second encode) and outputs are unchanged."""
    cfg = _encdec_cfg()
    params = model.init_params(cfg, PLAN)
    rng = np.random.RandomState(7)
    fr = rng.randn(cfg.enc_seq_len, cfg.d_model).astype(np.float32)
    base = [(rng.randint(2, cfg.vocab_size, L).astype(np.int32), m, fr)
            for L, m in zip([11, 8], [6, 5], strict=True)]

    def run(preempt_at):
        eng = ServingEngine.build_paged(cfg, PLAN, mesh1, 2, 32, params,
                                        page_size=8, prefill_chunk=8)
        reqs = _mk_requests(base)
        for r in reqs:
            eng.submit(r)
        tick = 0
        while (eng.has_pending() or
               any(a is not None for a in eng.admissions)) and tick < 500:
            if tick in preempt_at and eng.admissions[0] is not None:
                eng.preempt(0)
            eng.tick()
            tick += 1
        assert all(r.done for r in reqs)
        return {r.rid: tuple(r.out_tokens) for r in reqs}, eng

    ref, _ = run(set())
    got, eng = run({2})
    assert got == ref
    assert eng.stats.preemptions == 1
    # one encode for the shared frames; the resume was a cross-cache hit
    assert eng.stats.cross_encodes == 1
    _assert_leak_free(eng)


# ---------------------------------------------------------------------------
# leak-freedom property: policies x dp with preemption, slabs included
# ---------------------------------------------------------------------------

@pytest.mark.slow
@pytest.mark.parametrize("dp", [1, 2])
@pytest.mark.parametrize("policy", ["fcfs", "priority", "fair"])
def test_slab_and_page_leak_freedom(policy, dp, mesh1):
    """Randomized hybrid workload across fcfs/priority/fair and dp={1,2}:
    after run() + drain(), every replica's pages and slabs are released
    (the leak-freedom property of PR 3/4 extended to slabs)."""
    cfg = _hybrid_cfg()
    params = model.init_params(cfg, PLAN)
    scheduler = {"fcfs": None,
                 "priority": functools.partial(PriorityScheduler,
                                               preemption=True),
                 "fair": functools.partial(FairScheduler, preemption=True,
                                           quantum=16, preempt_after=1),
                 }[policy]
    eng = ServingEngine.build_paged(cfg, PLAN, mesh1, 2, 32, params,
                                    page_size=8, prefill_chunk=8,
                                    scheduler=scheduler, dp=dp)
    rng = np.random.RandomState(11 + dp)
    reqs = []
    for rid in range(8):
        L = int(rng.randint(1, 20))
        reqs.append(Request(
            rid=rid, prompt=rng.randint(2, cfg.vocab_size, L).astype(np.int32),
            max_new_tokens=int(rng.randint(1, 6)),
            priority=int(rng.randint(0, 3)), client_id=rid % 3))
    for r in reqs:
        eng.submit(r)
    # a tight tick budget leaves work in flight -> drain must reclaim it
    eng.run(max_ticks=int(rng.randint(3, 30)))
    eng.drain()
    _assert_leak_free(eng)


# ---------------------------------------------------------------------------
# precise errors for unsupported combinations
# ---------------------------------------------------------------------------

def test_prefix_cache_with_ssm_arch_raises_precisely(mesh1):
    cfg = _hybrid_cfg()
    params = model.init_params(cfg, PLAN)
    with pytest.raises(ValueError, match="SSM layers hold recurrent state"):
        ServingEngine.build_paged(cfg, PLAN, mesh1, 2, 32, params,
                                  page_size=8, prefill_chunk=8,
                                  prefix_cache=True)


def test_prefix_cache_with_encdec_arch_raises_precisely(mesh1):
    cfg = _encdec_cfg()
    params = model.init_params(cfg, PLAN)
    with pytest.raises(ValueError, match="encoder frames"):
        ServingEngine.build_paged(cfg, PLAN, mesh1, 2, 32, params,
                                  page_size=8, prefill_chunk=8,
                                  prefix_cache=True)


def test_vision_arch_rejected_precisely(mesh1):
    cfg = reduced(get_config("pixtral-12b"), dtype="float32")
    with pytest.raises(ValueError, match="vision"):
        ServingEngine.build_paged(cfg, PLAN, mesh1, 2, 32, params=None,
                                  page_size=8, prefill_chunk=8)


def test_encdec_request_without_frames_raises(mesh1):
    cfg = _encdec_cfg()
    params = model.init_params(cfg, PLAN)
    eng = ServingEngine.build_paged(cfg, PLAN, mesh1, 1, 32, params,
                                    page_size=8, prefill_chunk=8)
    with pytest.raises(RuntimeError, match="frames"):
        eng.submit(Request(rid=0, prompt=np.arange(2, 8, dtype=np.int32)))
    bad = np.zeros((3, 3), np.float32)
    with pytest.raises(RuntimeError, match="frames shape"):
        eng.submit(Request(rid=1, prompt=np.arange(2, 8, dtype=np.int32),
                           frames=bad))
