"""Scheduling subsystem: priority + aging admission, DRR fairness,
preemption (page donation to the prefix cache, requeue, resume-as-hit),
drain leak-freedom, the first-token emission fix, and schedule-invariance
properties — greedy outputs are token-identical across fcfs/priority/fair
and invariant to forced preemption points; sampled outputs are invariant
to admission order via per-request RNG streams."""
import numpy as np
import pytest
from greedy_ref import greedy_reference

from repro.configs import get_config, reduced
from repro.core import model
from repro.core.kvcache import PageAllocator
from repro.core.partition import ShardingPlan
from repro.serving.policies import FairScheduler, PriorityScheduler
from repro.serving.prefix_cache import RadixPrefixCache
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


def _pool():
    """Scheduler pool keywords for the policy tests: room for four slots
    at the 64-token budget, so no admission here blocks on pages."""
    return {"allocator": PageAllocator(4 * 64 // PSZ + 1), "page_size": PSZ}


# ---------------------------------------------------------------------------
# priority: ordering + aging
# ---------------------------------------------------------------------------

def test_priority_admission_order():
    sched = PriorityScheduler(seq_budget=64, **_pool())
    for rid, p in enumerate([0, 5, 1, 5]):
        sched.submit(_Req(rid, toks(1, 2, 3), priority=p))
    order = [a.req.rid for a in sched.plan([0, 1, 2, 3])]
    # descending priority; ties in submission order
    assert order == [1, 3, 2, 0]


def test_priority_aging_prevents_starvation():
    """A continuous high-priority stream must not starve a low-priority
    request: its aged effective priority eventually wins the round."""
    sched = PriorityScheduler(seq_budget=64, aging_rate=0.25, **_pool())
    low = _Req(0, toks(1, 2, 3), priority=0)
    sched.submit(low)
    admitted_round = None
    for r in range(1, 100):
        sched.submit(_Req(100 + r, toks(4, 5, 6), priority=5))
        (adm,) = sched.plan([0])
        sched.on_finish(adm)
        if adm.req is low:
            admitted_round = r
            break
    assert admitted_round is not None and admitted_round <= 30

    # and with aging off, it starves forever
    sched0 = PriorityScheduler(seq_budget=64, aging_rate=0.0, **_pool())
    low0 = _Req(0, toks(1, 2, 3), priority=0)
    sched0.submit(low0)
    for r in range(1, 100):
        sched0.submit(_Req(100 + r, toks(4, 5, 6), priority=5))
        (adm,) = sched0.plan([0])
        sched0.on_finish(adm)
        assert adm.req is not low0


# ---------------------------------------------------------------------------
# fairness: deficit round-robin
# ---------------------------------------------------------------------------

def test_fair_drr_interleaves_clients():
    """A flooding client shares the slot evenly with a light client."""
    sched = FairScheduler(seq_budget=64, quantum=16, **_pool())
    for rid in range(6):                           # client 0 floods
        sched.submit(_Req(rid, toks(*range(8)), max_new=4, client_id=0))
    for rid in (10, 11):                           # client 1: two requests
        sched.submit(_Req(rid, toks(*range(8)), max_new=4, client_id=1))
    order = []
    while sched.has_pending():
        (adm,) = sched.plan([0])
        order.append(adm.req.rid)
        sched.on_finish(adm)
    # interleaved while both are backlogged, FIFO within each client
    assert order == [0, 10, 1, 11, 2, 3, 4, 5]


def test_fair_drr_charges_by_cost():
    """A client with 3x-heavier requests gets ~1/3 the admission rate."""
    sched = FairScheduler(seq_budget=64, quantum=12, **_pool())
    for rid in range(4):                           # heavy: cost 36
        sched.submit(_Req(rid, toks(*range(30)), max_new=6, client_id=0))
    for rid in range(10, 22):                      # light: cost 12
        sched.submit(_Req(rid, toks(*range(8)), max_new=4, client_id=1))
    order = []
    while sched.has_pending():
        (adm,) = sched.plan([0])
        order.append(adm.req.rid)
        sched.on_finish(adm)
    # in any window where both clients are backlogged, the light client is
    # admitted ~3x as often: 9 light requests precede the 3rd heavy one
    assert sum(1 for r in order[:order.index(2)] if r >= 10) >= 8


# ---------------------------------------------------------------------------
# fairness: preemptive DRR (a running client must not starve a waiting one)
# ---------------------------------------------------------------------------

def test_fair_drr_preempts_long_running_client():
    """Client 0's long-running requests occupy every slot; client 1 arrives
    and accrues deficit until it evicts client 0's most recent admission.
    Without ``preemption=True`` the same setup never preempts (the ROADMAP
    starvation bug)."""
    for preemption in (True, False):
        sched = FairScheduler(seq_budget=64, quantum=16, preemption=preemption,
                              preempt_after=3, **_pool())
        for rid in range(4):               # client 0 floods both slots
            sched.submit(_Req(rid, toks(*range(8)), max_new=8, client_id=0))
        adms = sched.plan([0, 1])
        assert [a.req.client_id for a in adms] == [0, 0]
        sched.submit(_Req(10, toks(*range(8)), max_new=8, client_id=1))
        victims = []
        for _ in range(20):                # no free slots: decode-only ticks
            victims = sched.plan_preemptions(adms, 0)
            if victims:
                break
        if not preemption:
            assert victims == []
            continue
        assert len(victims) == 1
        victim = victims[0]
        assert victim.req.client_id == 0
        # the most recently admitted of client 0's slots: least sunk work
        assert victim.seq == max(a.seq for a in adms)
        sched.on_preempt(victim, effective_prompt(victim.req)[:0])
        active = [a for a in adms if a is not victim]
        (adm1,) = sched.plan([victim.slot])
        assert adm1.req.client_id == 1     # the starved client gets the slot
        # no immediate ping-pong: client 0 was just served and client 1's
        # deficit was charged at admission, so the next tick evicts nobody
        assert sched.plan_preemptions(active + [adm1], 0) == []


def test_fair_drr_preemption_respects_free_slots():
    """A usable free slot serves the waiting client without eviction."""
    sched = FairScheduler(seq_budget=64, quantum=16, preemption=True,
                          preempt_after=1, **_pool())
    sched.submit(_Req(0, toks(*range(8)), max_new=8, client_id=0))
    (adm,) = sched.plan([0, 1])
    sched.submit(_Req(1, toks(*range(8)), max_new=8, client_id=1))
    for _ in range(10):                    # slot 1 stays free throughout
        assert sched.plan_preemptions([adm], 1) == []


# ---------------------------------------------------------------------------
# preemption: victim choice, no ping-pong, page donation + resume-as-hit
# ---------------------------------------------------------------------------

def test_preemption_victim_choice_and_no_ping_pong():
    sched = PriorityScheduler(seq_budget=64, preemption=True, **_pool())
    lo_a = _Req(0, toks(1, 2, 3), priority=1)
    lo_b = _Req(1, toks(4, 5, 6), priority=0)
    sched.submit(lo_a)
    sched.submit(lo_b)
    adms = sched.plan([0, 1])
    assert [a.req.rid for a in adms] == [0, 1]
    assert sched.plan_preemptions(adms, 0) == []   # nothing pending
    hi = _Req(2, toks(7, 8, 9), priority=5)
    sched.submit(hi)
    victims = sched.plan_preemptions(adms, 0)
    assert [v.req.rid for v in victims] == [1]     # lowest base priority
    sched.on_preempt(victims[0], effective_prompt(lo_b)[:0])
    (adm_hi,) = sched.plan([victims[0].slot])
    assert adm_hi.req is hi
    # the requeued victim (base 0) must NOT preempt back: active bases are
    # 1 and 5, both >= its own
    assert sched.plan_preemptions([adms[0], adm_hi], 0) == []
    # with a free slot available, pending work is served without eviction
    sched.submit(_Req(3, toks(1,), priority=9))
    assert sched.plan_preemptions([adms[0], adm_hi], 1) == []


def test_preemption_resets_victim_aging_no_ping_pong():
    """An aged-up victim must not out-rank the urgent request that
    displaced it: preemption resets its aging credit."""
    sched = PriorityScheduler(seq_budget=64, preemption=True, aging_rate=1.0,
                              **_pool())
    low = _Req(0, toks(1, 2, 3), priority=0)
    sched.submit(low)
    for _ in range(20):                  # age low well past priority 10
        sched.plan([])
    (adm_low,) = sched.plan([0])         # the aged request wins a FREE slot
    assert adm_low.req is low
    hi = _Req(1, toks(4, 5, 6), priority=10)
    sched.submit(hi)
    (victim,) = sched.plan_preemptions([adm_low], 0)
    assert victim.req is low
    sched.on_preempt(victim, effective_prompt(low)[:0])
    (adm_hi,) = sched.plan([0])          # the freed slot goes to hi...
    assert adm_hi.req is hi
    assert sched.plan_preemptions([adm_hi], 0) == []   # ...and stays there


def test_preemption_scans_past_aged_low_priority_head():
    """A fresh high-priority request behind an aged low-priority one in
    the pending order must still trigger preemption."""
    sched = PriorityScheduler(seq_budget=64, preemption=True, aging_rate=1.0,
                              **_pool())
    running = _Req(0, toks(1,), priority=0)
    sched.submit(running)
    (adm,) = sched.plan([0])
    aged = _Req(1, toks(2,), priority=0)
    sched.submit(aged)
    for _ in range(20):                  # aged's effective priority ~20
        sched.plan([])
    sched.submit(_Req(2, toks(3,), priority=10))
    (victim,) = sched.plan_preemptions([adm], 0)
    assert victim.req is running


def test_preemption_fires_under_page_pressure_despite_free_slot():
    """A free slot whose pool is exhausted must not suppress preemption —
    evicting the victim is what frees the pages."""
    alloc = PageAllocator(9)             # 8 usable
    sched = PriorityScheduler(seq_budget=32, allocator=alloc, page_size=PSZ,
                              prefix_cache=None, stats=None, preemption=True)
    low = _Req(0, toks(*range(16)), max_new=8, priority=0)   # 6 pages
    sched.submit(low)
    (adm,) = sched.plan([0, 1])
    assert len(adm.pages) == 6           # 2 pages left, slot 1 free
    hi = _Req(1, toks(*range(8)), max_new=8, priority=10)    # needs 4
    sched.submit(hi)
    (victim,) = sched.plan_preemptions([adm], 1)
    assert victim.req is low
    sched.on_preempt(victim, effective_prompt(low)[:0])
    (adm_hi,) = sched.plan([0, 1])       # low re-blocks; hi admitted
    assert adm_hi.req is hi and len(adm_hi.pages) == 4
    sched.on_finish(adm_hi)


def test_preemption_donates_pages_and_resumes_as_prefix_hit():
    alloc = PageAllocator(16)                      # 15 usable
    cache = RadixPrefixCache(alloc, PSZ)
    sched = PriorityScheduler(seq_budget=64, allocator=alloc, page_size=PSZ,
                              prefix_cache=cache, stats=None,
                              preemption=True)
    req = _Req(0, toks(*range(10, 18)), max_new=8)   # 8 + 8 -> 4 pages
    sched.submit(req)
    (adm,) = sched.plan([0])
    assert len(adm.pages) == 4 and adm.cached_len == 0
    sched.on_prefill_complete(adm)                 # prompt pages cached
    req.out_tokens = [91, 92, 93, 94, 95]          # decode progress: pos 12
    resident = effective_prompt(req)[:12]          # 3 full pages resident
    sched.on_preempt(adm, resident)
    # slot refs dropped; 3 pages survive cache-held, the partial tail freed
    assert alloc.n_free == 15 - 3
    assert cache.n_cached_pages == 3
    assert sched.has_pending()                     # requeued
    (adm2,) = sched.plan([0])
    assert adm2.req is req
    # resume is a prefix hit on the donated pages — prompt AND generated
    # KV reused, only the partial tail re-prefilled
    assert adm2.cached_len == 12 and adm2.cow is None
    assert adm2.pages[:3] == adm.pages[:3]
    sched.on_finish(adm2)
    cache.evict(10 ** 6)
    assert alloc.n_free == 15                      # leak-free


# ---------------------------------------------------------------------------
# randomized property: conservation + allocator leak-freedom under random
# admission, prefill completion, finish, and forced preemption, per policy
# ---------------------------------------------------------------------------

def _policies():
    return [
        ("fcfs", lambda **kw: FCFSScheduler(**kw)),
        ("priority", lambda **kw: PriorityScheduler(preemption=True, **kw)),
        ("fair", lambda **kw: FairScheduler(quantum=8, **kw)),
    ]


@pytest.mark.parametrize("name,mk", _policies(),
                         ids=[p[0] for p in _policies()])
def test_policies_conserve_requests_and_pages_randomized(name, mk):
    for seed in range(4):
        rng = np.random.RandomState(seed)
        alloc = PageAllocator(33)                  # 32 usable
        cache = RadixPrefixCache(alloc, PSZ)
        sched = mk(seq_budget=64, allocator=alloc, page_size=PSZ,
                   prefix_cache=cache, stats=None)
        reqs = [_Req(rid, toks(*rng.randint(2, 50, rng.randint(1, 13))),
                     max_new=int(rng.randint(1, 7)),
                     priority=int(rng.randint(0, 4)),
                     client_id=int(rng.randint(0, 3)))
                for rid in range(20)]
        for r in reqs:
            sched.submit(r)
        active, finished, preempts = {}, set(), 0
        for _step in range(5000):
            if len(finished) == len(reqs):
                break
            free = [s for s in range(3) if s not in active]
            for adm in sched.plan(free):
                if adm.cow is not None:            # engine copies, then:
                    sched.on_cow_done(adm)
                active[adm.slot] = [adm, False]    # prefill still pending
            for slot in list(active):
                adm, prefilled = active[slot]
                req = adm.req
                act = rng.rand()
                if act < 0.15 and preempts < 60:   # forced preemption
                    n = (len(req.prompt) + len(req.out_tokens) - 1
                         if prefilled and req.out_tokens else
                         int(rng.randint(0, len(req.prompt) + 1)))
                    sched.on_preempt(adm, effective_prompt(req)[:max(n, 0)])
                    del active[slot]
                    preempts += 1
                elif not prefilled:
                    sched.on_prefill_complete(adm)
                    active[slot][1] = True
                    req.out_tokens.append(int(rng.randint(2, 50)))
                    if len(req.out_tokens) >= req.max_new_tokens:
                        sched.on_finish(adm)
                        finished.add(req.rid)
                        del active[slot]
                else:
                    req.out_tokens.append(int(rng.randint(2, 50)))
                    if len(req.out_tokens) >= req.max_new_tokens:
                        sched.on_finish(adm)
                        finished.add(req.rid)
                        del active[slot]
        # conservation: every request finished exactly once, none lost
        # across preemptions/requeues
        assert finished == {r.rid for r in reqs}, (name, seed)
        # leak-freedom: every page is either free or cache-held
        assert alloc.n_free + cache.n_cached_pages == 32, (name, seed)
        cache.evict(10 ** 6)
        assert alloc.n_free == 32, (name, seed)


@pytest.mark.parametrize("name,mk", _policies(),
                         ids=[p[0] for p in _policies()])
def test_blocked_head_is_not_overtaken(name, mk):
    """The policy's next pick B needs more pages than are free: a plan()
    with two free slots admits neither B nor the smaller C behind it, and
    rolls back whatever it took.  Once A returns its pages, B goes first."""
    alloc = PageAllocator(9)                       # 8 usable
    sched = mk(seq_budget=32, allocator=alloc, page_size=PSZ,
               prefix_cache=None, stats=None)
    a = _Req(0, toks(*range(16)), max_new=8, priority=9)      # 6 pages
    sched.submit(a)
    (adm_a,) = sched.plan([0])
    assert alloc.n_free == 2
    b = _Req(1, toks(*range(8)), max_new=8, priority=5)       # 4 pages
    c = _Req(2, toks(1, 2), max_new=2, priority=0)            # 1 page
    sched.submit(b)
    sched.submit(c)
    assert sched.plan([1, 2]) == []
    assert alloc.n_free == 2 and sched.has_pending()
    sched.on_finish(adm_a)
    adms = sched.plan([1, 2])
    assert adms[0].req is b
    for x in adms:
        sched.on_finish(x)
    assert alloc.n_free == 8


# ---------------------------------------------------------------------------
# engine level
# ---------------------------------------------------------------------------

def _reqs_mixed(cfg, n=7, seed=0):
    from repro.serving import Request
    rng = np.random.RandomState(seed)
    prios = [0, 3, 1, 0, 5, 2, 0]
    out = []
    for rid in range(n):
        L = int(rng.randint(4, 20))
        out.append(Request(rid=rid,
                           prompt=rng.randint(2, cfg.vocab_size,
                                              L).astype(np.int32),
                           max_new_tokens=int(rng.randint(2, 7)),
                           priority=prios[rid % len(prios)],
                           client_id=rid % 3))
    return out


def _run_paged(cfg, params, mesh1, scheduler=None, reqs=None, sampler=None,
               prefix_cache=False, slots=2):
    from repro.serving import ServingEngine
    eng = ServingEngine.build_paged(cfg, PLAN, mesh1, slots, 64, params,
                                    page_size=8, prefill_chunk=16,
                                    prefix_cache=prefix_cache,
                                    scheduler=scheduler, sampler=sampler)
    for r in reqs:
        eng.submit(r)
    eng.run(max_ticks=5000)
    return eng


@pytest.mark.slow
def test_greedy_token_identical_across_policies(mesh1):
    """fcfs / priority / fair reorder admissions, never tokens."""
    cfg = reduced(get_config("tinyllama-42m"), dtype="float32")
    params = model.init_params(cfg, PLAN)
    outs = []
    for sched in (None,
                  lambda **kw: PriorityScheduler(**kw),
                  lambda **kw: FairScheduler(**kw)):
        reqs = _reqs_mixed(cfg)
        _run_paged(cfg, params, mesh1, scheduler=sched, reqs=reqs)
        assert all(r.done for r in reqs)
        outs.append({r.rid: tuple(r.out_tokens) for r in reqs})
    assert outs[0] == outs[1] == outs[2]


@pytest.mark.slow
def test_forced_preemption_identity_and_kv_reuse(mesh1):
    """A preempted-and-resumed request emits exactly the uncontended
    continuation, and its KV (prompt AND generated) is reused via the
    prefix cache, not recomputed."""
    from repro.serving import Request, ServingEngine

    cfg = reduced(get_config("tinyllama-42m"), dtype="float32")
    params = model.init_params(cfg, PLAN)
    rng = np.random.RandomState(5)
    p1 = rng.randint(2, cfg.vocab_size, 12).astype(np.int32)
    p2 = rng.randint(2, cfg.vocab_size, 20).astype(np.int32)

    def mk():
        return [Request(rid=0, prompt=p1.copy(), max_new_tokens=8),
                Request(rid=1, prompt=p2.copy(), max_new_tokens=4)]

    ref = mk()
    ref_eng = ServingEngine.build_paged(cfg, PLAN, mesh1, 1, 64, params,
                                        page_size=8, prefill_chunk=8,
                                        prefix_cache=True)
    for r in ref:
        ref_eng.submit(r)
    ref_eng.run(max_ticks=5000)
    ref_out = {r.rid: tuple(r.out_tokens) for r in ref}

    eng = ServingEngine.build_paged(cfg, PLAN, mesh1, 1, 64, params,
                                    page_size=8, prefill_chunk=8,
                                    prefix_cache=True)
    r1, r2 = mk()
    eng.submit(r1)
    eng.submit(r2)
    # preempt r1 mid-decode, after its output spills into a generated page
    for _ in range(200):
        if len(r1.out_tokens) >= 6:
            break
        eng.tick()
    assert eng.admissions[0].req is r1 and not r1.done
    eng.preempt(0)
    # preempt r2 mid-prefill (its 20-token prompt spans 3 chunks)
    for _ in range(500):
        adm = eng.admissions[0]
        if adm is not None and adm.req is r2 and \
                eng.slot_state[0] == "prefill" and eng.prefill_done[0] > 0 \
                and not r2.done:
            break
        eng.tick()
    assert eng.admissions[0].req is r2
    eng.preempt(0)
    stats = eng.run(max_ticks=5000)
    assert r1.done and r2.done
    assert {0: tuple(r1.out_tokens), 1: tuple(r2.out_tokens)} == ref_out
    assert stats.preemptions == 2
    # r1 was preempted at pos 12+6-1=17 -> 2 full pages donated; resume
    # skipped at least those 16 tokens instead of recomputing them
    assert stats.prefill_tokens_skipped >= 16
    # leak-freedom: every page free or cache-held
    usable = eng.allocator.n_pages - eng.allocator.n_reserved
    assert eng.allocator.n_free + eng.prefix_cache.n_cached_pages == usable


@pytest.mark.slow
def test_sampled_outputs_schedule_invariant(mesh1):
    """Per-request RNG streams: non-greedy outputs are identical even when
    the policy reverses admission order."""
    from repro.serving import SamplerConfig
    cfg = reduced(get_config("tinyllama-42m"), dtype="float32")
    params = model.init_params(cfg, PLAN)
    sampler = SamplerConfig(temperature=0.7, top_k=8)
    rng = np.random.RandomState(9)
    prompts = [rng.randint(2, cfg.vocab_size, int(rng.randint(4, 14))
                           ).astype(np.int32) for _ in range(5)]

    def mk(prio_by_rid):
        from repro.serving import Request
        return [Request(rid=i, prompt=p.copy(), max_new_tokens=5,
                        priority=prio_by_rid(i))
                for i, p in enumerate(prompts)]

    a = mk(lambda i: 0)                           # FCFS: submission order
    _run_paged(cfg, params, mesh1, reqs=a, sampler=sampler)
    b = mk(lambda i: i)                           # priority: reversed order
    _run_paged(cfg, params, mesh1,
               scheduler=lambda **kw: PriorityScheduler(**kw), reqs=b,
               sampler=sampler)
    assert {r.rid: tuple(r.out_tokens) for r in a} == \
           {r.rid: tuple(r.out_tokens) for r in b}


@pytest.mark.slow
def test_first_token_from_prefill_logits_and_exact_budget(mesh1):
    """The token sampled from the prompt's final logits is the first output
    token (not silently dropped), and max_new_tokens is exact."""
    from repro.serving import Request, ServingEngine
    cfg = reduced(get_config("qwen3-0.6b"), dtype="float32")
    params = model.init_params(cfg, PLAN)
    SB = 32
    prompt = np.arange(2, 11, dtype=np.int32)
    (t0,) = greedy_reference(
        cfg, PLAN, mesh1, params,
        [Request(rid=0, prompt=prompt, max_new_tokens=1)], SB)[0]

    eng = ServingEngine.build_paged(cfg, PLAN, mesh1, 1, SB, params,
                                    page_size=8, prefill_chunk=8)
    req = Request(rid=0, prompt=prompt, max_new_tokens=3)
    eng.submit(req)
    stats = eng.run(max_ticks=50)
    assert req.done
    assert req.out_tokens[0] == t0
    assert len(req.out_tokens) == 3               # exact, not off by one
    assert 0 in stats.request_ttft                # TTFT at prefill complete

    # a max_new_tokens=1 request completes at prefill, no decode tick needed
    req1 = Request(rid=1, prompt=prompt.copy(), max_new_tokens=1)
    eng.submit(req1)
    eng.run(max_ticks=50)
    assert req1.done and req1.out_tokens == [t0]


@pytest.mark.slow
def test_drain_releases_stranded_pages(mesh1):
    """run(max_ticks) exhaustion strands admitted slots; drain() routes
    them through on_finish and the allocator ends leak-free."""
    from repro.serving import Request, ServingEngine
    cfg = reduced(get_config("tinyllama-42m"), dtype="float32")
    params = model.init_params(cfg, PLAN)
    for prefix_cache in (False, True):
        eng = ServingEngine.build_paged(cfg, PLAN, mesh1, 2, 64, params,
                                        page_size=8, prefill_chunk=16,
                                        prefix_cache=prefix_cache)
        rng = np.random.RandomState(0)
        reqs = [Request(rid=i,
                        prompt=rng.randint(2, cfg.vocab_size,
                                           12).astype(np.int32),
                        max_new_tokens=8) for i in range(4)]
        for r in reqs:
            eng.submit(r)
        eng.run(max_ticks=2)                      # strands work mid-flight
        assert any(a is not None for a in eng.admissions)
        usable = eng.allocator.n_pages - eng.allocator.n_reserved
        assert eng.allocator.n_free < usable      # pages genuinely held
        n = eng.drain()
        assert n > 0 and all(a is None for a in eng.admissions)
        cached = eng.prefix_cache.n_cached_pages if prefix_cache else 0
        assert eng.allocator.n_free + cached == usable
