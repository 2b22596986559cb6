"""Elastic serving under live traffic: replica join (``scale_to`` up),
drain (``scale_to`` down migrates in-flight KV pages to survivors),
crash (``kill_replica`` re-admits orphans as re-prefills), host-side
spill/restore of radix + cross-KV state, and a seeded chaos property
test: across hundreds of random membership schedules, every request
completes with output token-identical to the dp=1 serial oracle and
every replica drains leak-free.

Schedules are driven by ``faultlib.FaultPlan`` through the engine's
``membership_hook`` (fires at the top of each tick, where membership
changes barrier the overlapped pipeline first), so each schedule replays
exactly from its seed; ``--chaos-seed`` / ``CHAOS_SCHEDULES`` reshuffle
or resize the sweep."""
import os

import numpy as np
import pytest
from faultlib import FaultPlan, inject_transfer_fault

from repro.configs import get_config, reduced
from repro.core import model
from repro.core.kvcache import pages_needed
from repro.core.partition import ShardingPlan
from repro.serving import (FairScheduler, HostSpillStore, PriorityScheduler,
                           Request, ServingEngine)
from repro.serving.sampler import SamplerConfig

PLAN = ShardingPlan(tp=1, kv_cache_dtype="float32")
PLAN_I8 = ShardingPlan(tp=1, kv_cache_dtype="int8")
PLANS = {"fp32": PLAN, "int8": PLAN_I8}

N_SCHEDULES = int(os.environ.get("CHAOS_SCHEDULES", "200"))

_SCHEDULERS = {
    "fcfs": None,
    "priority": lambda **kw: PriorityScheduler(preemption=True, **kw),
    "fair": lambda **kw: FairScheduler(**kw),
}


@pytest.fixture(scope="module")
def cfg():
    return reduced(get_config("tinyllama-42m"), dtype="float32")


@pytest.fixture(scope="module")
def params(cfg):
    return {tag: model.init_params(cfg, plan) for tag, plan in PLANS.items()}


def _requests(cfg, n=6, seed=0, max_new=(2, 8)):
    rng = np.random.RandomState(seed)
    return [Request(rid=rid,
                    prompt=rng.randint(2, cfg.vocab_size,
                                       int(rng.randint(4, 20)))
                    .astype(np.int32),
                    max_new_tokens=int(rng.randint(*max_new)),
                    priority=int(rng.randint(0, 3)),
                    client_id=int(rng.randint(0, 3)))
            for rid in range(n)]


def _build(cfg, plan, mesh1, params, dp, slots=2, policy="fcfs", **kw):
    return ServingEngine.build_paged(cfg, plan, mesh1, slots, 64, params,
                                     page_size=8, prefill_chunk=16,
                                     prefix_cache=True, dp=dp,
                                     scheduler=_SCHEDULERS[policy], **kw)


def _assert_leak_free(eng):
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


# dp=1 serial oracles, computed once per (plan, request-seed, sampler) and
# shared across all chaos schedules that replay the same request set
_ORACLES = {}


def _oracle(cfg, mesh1, params, tag, req_seed, sampler=None, rng_seed=0):
    key = (tag, req_seed, sampler is not None, rng_seed)
    if key not in _ORACLES:
        reqs = _requests(cfg, seed=req_seed)
        eng = _build(cfg, PLANS[tag], mesh1, params[tag], dp=1,
                     overlap=False, sampler=sampler, rng_seed=rng_seed)
        for r in reqs:
            eng.submit(r)
        eng.run(max_ticks=5000)
        assert all(r.done for r in reqs)
        _ORACLES[key] = {r.rid: tuple(r.out_tokens) for r in reqs}
    return _ORACLES[key]


def _outputs(reqs):
    return {r.rid: tuple(r.out_tokens) for r in reqs}


# ---------------------------------------------------------------------------
# chaos property test
# ---------------------------------------------------------------------------

def test_chaos_schedules_complete_and_match_oracle(cfg, params, mesh1,
                                                   pytestconfig):
    """The headline property: under randomized membership schedules —
    scale-down drains with page migration, scale-up joins, crashes with
    re-admission, layered over all three scheduling policies and both KV
    dtypes — every request completes, greedy outputs are token-identical
    to the dp=1 serial oracle, and a post-run drain leaves every replica
    leak-free."""
    base = int(pytestconfig.getoption("--chaos-seed"))
    applied = {"scale": 0, "kill": 0}
    for i in range(N_SCHEDULES):
        rng = np.random.RandomState([base, i])
        tag = ("fp32", "int8")[rng.randint(2)]
        policy = ("fcfs", "priority", "fair")[rng.randint(3)]
        dp0 = int(rng.randint(2, 4))
        req_seed = int(rng.randint(4))
        ref = _oracle(cfg, mesh1, params, tag, req_seed)
        reqs = _requests(cfg, seed=req_seed)
        eng = _build(cfg, PLANS[tag], mesh1, params[tag], dp=dp0,
                     policy=policy)
        plan = FaultPlan.random(rng).install(eng)
        for r in reqs:
            eng.submit(r)
        eng.run(max_ticks=5000)
        ctx = (i, tag, policy, dp0, req_seed, plan.events)
        assert all(r.done for r in reqs), ctx
        assert _outputs(reqs) == ref, ctx
        eng.drain()
        _assert_leak_free(eng)
        for _, kind, _v in plan.applied:
            applied[kind] += 1
    # the sweep must actually exercise both event kinds, or the property
    # silently degrades to plain dp serving (tiny CHAOS_SCHEDULES debug
    # sweeps are exempt — too few draws to guarantee both)
    if N_SCHEDULES >= 20:
        assert applied["scale"] > 0 and applied["kill"] > 0, applied


def test_sampled_outputs_schedule_invariant(cfg, params, mesh1):
    """Per-request RNG streams make sampled outputs a function of the
    request alone: two different membership schedules (and the serial
    dp=1 run) produce identical sampled tokens."""
    samp = SamplerConfig(temperature=0.8, top_k=40)
    ref = _oracle(cfg, mesh1, params, "fp32", 2, sampler=samp, rng_seed=7)
    for dp0, events in ((2, [(3, "scale", 1), (8, "scale", 2)]),
                        (3, [(4, "kill", 1)])):
        reqs = _requests(cfg, seed=2)
        eng = _build(cfg, PLAN, mesh1, params["fp32"], dp=dp0,
                     sampler=samp, rng_seed=7)
        FaultPlan(events).install(eng)
        for r in reqs:
            eng.submit(r)
        eng.run(max_ticks=5000)
        assert all(r.done for r in reqs)
        assert _outputs(reqs) == ref, (dp0, events)
        _assert_leak_free(eng)


# ---------------------------------------------------------------------------
# targeted membership-change units
# ---------------------------------------------------------------------------

def test_scale_down_mid_overlap_completes_all(cfg, params, mesh1):
    """``scale_to`` called while a dispatched tick is still in flight must
    barrier first (collect the pending plan) before moving any state — no
    request is dropped and outputs match the serial oracle."""
    ref = _oracle(cfg, mesh1, params, "fp32", 0)
    reqs = _requests(cfg, seed=0)
    eng = _build(cfg, PLAN, mesh1, params["fp32"], dp=2, overlap=True)
    for r in reqs:
        eng.submit(r)
    for _ in range(50):
        eng.tick()
        if eng._inflight is not None:
            break
    assert eng._inflight is not None, "pipeline never went in flight"
    eng.scale_to(1)
    assert eng.R == 1 and eng._inflight is None
    eng.run(max_ticks=5000)
    assert all(r.done for r in reqs)
    assert _outputs(reqs) == ref
    assert eng.stats.scale_events == 1
    eng.drain()
    _assert_leak_free(eng)


def test_scale_down_migrates_pages(cfg, params, mesh1):
    """With free slots on the survivor, draining moves resident KV pages
    via the transfer step instead of preempting — migrated requests keep
    their tokens (no re-prefill) and outputs still match the oracle."""
    ref = _oracle(cfg, mesh1, params, "int8", 1)
    reqs = _requests(cfg, seed=1)
    eng = _build(cfg, PLAN_I8, mesh1, params["int8"], dp=2, slots=4)
    for r in reqs:
        eng.submit(r)
    for _ in range(3):
        eng.tick()
    eng.scale_to(1)
    assert eng.stats.migrations > 0 and eng.stats.migrated_pages > 0
    eng.run(max_ticks=5000)
    assert all(r.done for r in reqs)
    assert _outputs(reqs) == ref
    eng.drain()
    _assert_leak_free(eng)


def test_migrated_slot_survives_preemption(cfg, params, mesh1):
    """Mid-migration preemption: a slot that just migrated to a survivor
    preempts and resumes there like any native admission."""
    ref = _oracle(cfg, mesh1, params, "fp32", 1)
    reqs = _requests(cfg, seed=1)
    eng = _build(cfg, PLAN, mesh1, params["fp32"], dp=2, slots=4)
    for r in reqs:
        eng.submit(r)
    for _ in range(3):
        eng.tick()
    eng.scale_to(1)
    assert eng.stats.migrations > 0
    b = next(b for b, adm in enumerate(eng.admissions) if adm is not None)
    eng.preempt(b)
    assert eng.stats.preemptions >= 1
    eng.run(max_ticks=5000)
    assert all(r.done for r in reqs)
    assert _outputs(reqs) == ref
    eng.drain()
    _assert_leak_free(eng)


def test_crash_during_handoff_rolls_back(cfg, params, mesh1):
    """A transfer fault mid-migration (after the destination admission is
    claimed, before the device copy) must roll back atomically: the
    destination claim is released, the source slot keeps serving, and the
    drain falls back to preemption — refcounts intact."""
    ref_reqs = _requests(cfg, seed=3, n=2)
    e1 = _build(cfg, PLAN, mesh1, params["fp32"], dp=1, overlap=False)
    for r in ref_reqs:
        e1.submit(r)
    e1.run(max_ticks=5000)
    ref = _outputs(ref_reqs)
    reqs = _requests(cfg, seed=3, n=2)
    eng = _build(cfg, PLAN, mesh1, params["fp32"], dp=2, overlap=False)
    for r in reqs:
        eng.submit(r)
    for _ in range(3):
        eng.tick()
    b_src = next(b for b, adm in enumerate(eng.admissions)
                 if adm is not None and b // eng.Bp == 1)
    free_before = eng.allocators[0].n_free
    rc_before = [eng.allocators[1].refcount(p)
                 for p in eng.admissions[b_src].pages]
    state = inject_transfer_fault(eng, fail_calls=range(1, 100))
    assert eng._migrate_slot(b_src, [0]) is False
    assert state["faults"] == 1
    # destination claim rolled back, source untouched
    assert eng.allocators[0].n_free == free_before
    assert eng.admissions[b_src] is not None
    assert [eng.allocators[1].refcount(p)
            for p in eng.admissions[b_src].pages] == rc_before
    # with the transfer step still failing, a full drain degrades to
    # preempt + re-admit — still no request lost
    eng.scale_to(1)
    assert eng.stats.migrations == 0
    assert eng.stats.transfer_failures == state["faults"] > 1
    eng.run(max_ticks=5000)
    assert all(r.done for r in reqs)
    assert _outputs(reqs) == ref
    eng.drain()
    _assert_leak_free(eng)


def test_transfer_step_errors_propagate(cfg, params, mesh1):
    """Only a transfer fault is survived and counted: an error raised by
    the transfer step itself — a ``TypeError``, as a tracing or compile
    failure raises — propagates out of the migration instead of
    degrading to preemption."""
    reqs = _requests(cfg, seed=3, n=2)
    eng = _build(cfg, PLAN, mesh1, params["fp32"], dp=2, overlap=False)
    for r in reqs:
        eng.submit(r)
    for _ in range(3):
        eng.tick()
    b_src = next(b for b, adm in enumerate(eng.admissions)
                 if adm is not None and b // eng.Bp == 1)
    state = inject_transfer_fault(eng, fail_calls=(1,))
    assert eng._migrate_slot(b_src, [0]) is False
    assert state["faults"] == 1 and eng.stats.transfer_failures == 1

    def broken_step(*args, **kwargs):
        raise TypeError("transfer step traced with a bad argument")

    eng.transfer_fn = broken_step
    with pytest.raises(TypeError, match="bad argument"):
        eng._migrate_slot(b_src, [0])
    assert eng.stats.transfer_failures == 1


def test_crash_readmits_exact_continuation(cfg, params, mesh1):
    """``kill_replica`` re-admits the dead replica's in-flight requests
    elsewhere as re-prefills over prompt+emitted — already-emitted tokens
    are kept, not regenerated, and the final outputs match the oracle."""
    ref = _oracle(cfg, mesh1, params, "fp32", 0)
    reqs = _requests(cfg, seed=0)
    eng = _build(cfg, PLAN, mesh1, params["fp32"], dp=2)
    for r in reqs:
        eng.submit(r)
    victim = None
    for _ in range(200):
        eng.tick()
        victim = next((r for r in reqs
                       if r.replica == 1 and r.out_tokens and not r.done),
                      None)
        if victim is not None:
            break
    assert victim is not None, "no replica-1 request ever emitted a token"
    emitted = list(victim.out_tokens)
    report = eng.kill_replica(1)
    assert report.replica == 1 and victim.rid in report.active_rids
    assert eng.R == 1 and eng.stats.crashes == 1
    eng.run(max_ticks=5000)
    assert all(r.done for r in reqs)
    assert victim.out_tokens[:len(emitted)] == emitted
    assert _outputs(reqs) == ref
    assert eng.stats.readmitted >= len(report.active_rids)
    eng.drain()
    _assert_leak_free(eng)


def test_admission_during_active_drain_avoids_draining_replica(cfg, params,
                                                               mesh1):
    """Router staleness regression: a replica marked draining must be
    excluded from placement even when it has the least page load."""
    eng = _build(cfg, PLAN, mesh1, params["fp32"], dp=2)
    busy = Request(rid=0, prompt=np.arange(2, 18, dtype=np.int32),
                   max_new_tokens=8)
    eng.submit(busy)
    eng.tick()
    assert busy.replica == 0
    # replica 1 is empty (least load) but draining — placement must skip it
    eng.router.mark_draining(1)
    late = Request(rid=1, prompt=np.arange(2, 8, dtype=np.int32),
                   max_new_tokens=2)
    eng.submit(late)
    assert late.replica == 0
    assert eng.router.decode_placement([0, 1]) == 0
    eng.run(max_ticks=5000)
    assert busy.done and late.done
    eng.drain()
    _assert_leak_free(eng)


# ---------------------------------------------------------------------------
# host-side spill/restore
# ---------------------------------------------------------------------------

def test_spill_restore_int8_byte_identity(cfg, params, mesh1):
    """Drain-time spill of a leaving replica's radix entries and restore
    into a survivor round-trips int8 payloads (and their scale rows)
    byte-identically — verified leaf-by-leaf against the pre-drain pages."""
    reqs = _requests(cfg, seed=1)
    store = HostSpillStore()
    eng = _build(cfg, PLAN_I8, mesh1, params["int8"], dp=2, spill=store)
    for r in reqs:
        eng.submit(r)
    eng.run(max_ticks=5000)
    eng._barrier()
    donor = next(r for r in range(2)
                 if eng.prefix_caches[r].n_cached_pages > 0)
    before = {}
    for toks, pages in eng.prefix_caches[donor].entries():
        before[toks] = [np.asarray(leaf[:, donor, eng._rows(leaf, pages)])
                        for leaf in eng._kind_leaves("kv")]
    assert before, "no radix entries to spill"
    keep = 1 - donor
    eng._drain_replicas([donor], [keep])
    eng._rebuild([keep], 1)
    eng._restore_from_spill(store)
    assert store.pages_saved > 0 and store.pages_restored > 0
    for toks, payloads in before.items():
        n, pages = eng.prefix_caches[0].lookup(list(toks))
        assert n == len(toks), "restored prefix not found"
        for leaf, want in zip(eng._kind_leaves("kv"), payloads):
            np.testing.assert_array_equal(
                np.asarray(leaf[:, 0, eng._rows(leaf, pages)]), want)
    _assert_leak_free(eng)


def test_spill_persists_radix_across_restart(cfg, params, mesh1):
    """An engine restart with the previous engine's spill store warm-starts
    the radix cache: a repeated prompt prefix skips prefill work."""
    reqs = _requests(cfg, seed=0, n=4)
    eng = _build(cfg, PLAN, mesh1, params["fp32"], dp=1)
    for r in reqs:
        eng.submit(r)
    eng.run(max_ticks=5000)
    store = eng.spill_state()
    assert store.n_entries > 0 and store.pages_saved > 0

    eng2 = _build(cfg, PLAN, mesh1, params["fp32"], dp=1, spill=store)
    assert store.pages_restored > 0
    again = [Request(rid=r.rid + 100, prompt=r.prompt.copy(),
                     max_new_tokens=int(r.max_new_tokens))
             for r in reqs]
    for r in again:
        eng2.submit(r)
    eng2.run(max_ticks=5000)
    assert all(r.done for r in again)
    assert _outputs(again) == {r.rid + 100: tuple(r.out_tokens)
                               for r in reqs}
    assert eng2.stats.prefix_hits > 0
    assert eng2.stats.prefill_tokens_skipped > 0
    eng2.drain()
    _assert_leak_free(eng2)


@pytest.mark.slow
def test_spill_persists_cross_kv_across_restart(mesh1):
    """Enc-dec: spilled cross-KV entries restore into a fresh engine, so
    a request with already-seen frames hits without re-encoding."""
    cfg = reduced(get_config("seamless-m4t-large-v2"), dtype="float32",
                  n_enc_layers=1, enc_seq_len=16)
    p = model.init_params(cfg, PLAN)
    rng = np.random.RandomState(3)
    frames = rng.randn(cfg.enc_seq_len, cfg.d_model).astype(np.float32)
    mk = lambda rid: Request(  # noqa: E731
        rid=rid, prompt=rng.randint(2, cfg.vocab_size, 7).astype(np.int32),
        max_new_tokens=3, frames=frames.copy())
    eng = ServingEngine.build_paged(cfg, PLAN, mesh1, 2, 32, p,
                                    page_size=8, prefill_chunk=8)
    r0 = mk(0)
    eng.submit(r0)
    eng.run(max_ticks=2000)
    assert eng.stats.cross_encodes == 1
    store = eng.spill_state()
    assert store.n_entries > 0

    eng2 = ServingEngine.build_paged(cfg, PLAN, mesh1, 2, 32, p,
                                     page_size=8, prefill_chunk=8,
                                     spill=store)
    r1 = mk(1)
    eng2.submit(r1)
    eng2.run(max_ticks=2000)
    assert r1.done
    assert eng2.stats.cross_hits == 1 and eng2.stats.cross_encodes == 0
    eng2.drain()
    _assert_leak_free(eng2)


# ---------------------------------------------------------------------------
# archs without a transfer path + validation
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_ssm_scale_down_preempts_and_recovers(mesh1):
    """Hybrid/SSM state lives in slabs the transfer step doesn't cover, so
    draining such replicas falls back to preempt + host stash — outputs
    still match the serial oracle and slabs stay leak-free."""
    cfg = reduced(get_config("mamba2-370m"), dtype="float32")
    p = model.init_params(cfg, PLAN)

    def build(dp):
        return ServingEngine.build_paged(cfg, PLAN, mesh1, 2, 64, p,
                                         page_size=8, prefill_chunk=16,
                                         n_pages=16, dp=dp,
                                         overlap=(dp > 1))

    ref = _requests(cfg, seed=4, n=4)
    e1 = build(1)
    for r in ref:
        e1.submit(r)
    e1.run(max_ticks=2000)

    reqs = _requests(cfg, seed=4, n=4)
    eng = build(2)
    FaultPlan([(3, "scale", 1)]).install(eng)
    for r in reqs:
        eng.submit(r)
    eng.run(max_ticks=2000)
    assert all(r.done for r in reqs)
    assert _outputs(reqs) == _outputs(ref)
    assert eng.stats.scale_events == 1 and eng.stats.migrations == 0
    eng.drain()
    _assert_leak_free(eng)


def test_scale_validation(cfg, params, mesh1):
    eng = _build(cfg, PLAN, mesh1, params["fp32"], dp=1)
    with pytest.raises(ValueError):
        eng.scale_to(0)
    with pytest.raises(ValueError):
        eng.kill_replica(0)            # cannot kill the last replica
    eng.scale_to(1)                    # no-op, not an error
    assert eng.stats.scale_events == 0

    disagg = ServingEngine.build_paged(cfg, PLANS["fp32"], mesh1, 1, 64,
                                       params["fp32"], page_size=8,
                                       prefill_chunk=16, dp=2,
                                       disagg=(1, 1))
    with pytest.raises(ValueError, match="disagg"):
        disagg.scale_to(1)


def test_pages_needed_budget_covers_migration(cfg, params, mesh1):
    """The migration plan's page budget (full effective prompt + remaining
    tokens) always covers the resident-KV transfer set."""
    reqs = _requests(cfg, seed=1)
    eng = _build(cfg, PLAN, mesh1, params["fp32"], dp=2, slots=4)
    for r in reqs:
        eng.submit(r)
    for _ in range(4):
        eng.tick()
    for b, adm in enumerate(eng.admissions):
        if adm is None:
            continue
        n = (eng.prefill_done[b] if eng.slot_state[b] == "prefill"
             else eng.pos[b])
        assert pages_needed(n, eng.page_size) <= len(adm.pages), b
    eng.run(max_ticks=5000)
    eng.drain()
    _assert_leak_free(eng)
