"""Serving throughput bench: contiguous vs paged vs paged+prefix-cache,
plus a mixed-priority QoS scenario (FCFS vs preemptive priority), a
dp-scaling scenario, a hybrid-arch (attention+SSM slab) row whose
outputs are asserted token-identical to the contiguous oracle, a
speculative-decoding row (prompt-lookup drafts + k-token verify) gated
on accepted tokens per verify tick staying above one, and a
disaggregated-serving scenario (dp=2 interleaved vs ``disagg=(1, 1)``)
gated on burst p99 TTFT decoupling from the decode tail at tokens/s
within tolerance, and an elastic scenario (replica crash + rejoin under
steady traffic) gated on the recovered-throughput ratio with post-crash
arrival TTFT fed to the regression gate.

Drives the full ServingEngine on a shared-system-prompt workload (every
request = common prefix + unique suffix — the traffic shape the radix
prefix cache targets) and reports tokens/s, TTFT, and prefix-cache
effectiveness (prefill tokens skipped, hit rate, COW copies).

The priority scenario saturates the slots with low-priority bulk
requests, lands a high-priority burst mid-run, and reports p50/p99 TTFT
per class under FCFS vs ``PriorityScheduler(preemption=True)`` — the
paper's interactive-wearable case, where sensor-triggered queries must
not queue behind bulk work.  Greedy low-priority outputs are asserted
token-identical across the two policies (preempted-and-resumed requests
produce exactly the uncontended continuation).

    PYTHONPATH=src python benchmarks/serving_bench.py --smoke --json \
        BENCH_serving.json

All prompts share one length so the contiguous oracle compiles once; the
paged modes would handle mixed lengths with the same single compile.
"""
from __future__ import annotations

import argparse
import json
import time

import numpy as np

# --smoke swaps in a tiny reduced config: same code path, CI-friendly wall
# time, and a BENCH_serving.json artifact for the perf trajectory.
SIZES = {
    "full": {"requests": 24, "slots": 4, "seq_budget": 256, "prefix": 96,
             "suffix": 24, "max_new": 24, "page_size": 16, "chunk": 32},
    "smoke": {"requests": 6, "slots": 2, "seq_budget": 64, "prefix": 24,
              "suffix": 6, "max_new": 6, "page_size": 8, "chunk": 16},
}


def build_requests(sz, vocab, seed=0):
    from repro.serving import Request
    rng = np.random.RandomState(seed)
    shared = rng.randint(2, vocab, sz["prefix"]).astype(np.int32)
    out = []
    for rid in range(sz["requests"]):
        suf = rng.randint(2, vocab, sz["suffix"]).astype(np.int32)
        out.append(Request(rid=rid,
                           prompt=np.concatenate([shared, suf]),
                           max_new_tokens=sz["max_new"]))
    return out


def _stats_row(mode, eng, stats, dt, n_requests):
    """The per-mode result row every scenario shares."""
    row = {"mode": mode,
           "requests": n_requests,
           "decoded_tokens": stats.decoded_tokens,
           "tokens_per_s": stats.decoded_tokens / dt,
           "ttft_p50_ms": float(np.median(stats.ttft_s)) * 1e3,
           "ttft_p95_ms": float(np.percentile(stats.ttft_s, 95)) * 1e3,
           "tpot_p50_ms": float(np.median(stats.tpot_s)) * 1e3,
           "prefill_tokens_skipped": stats.prefill_tokens_skipped,
           "prefix_hit_rate": stats.prefix_hit_rate,
           "cow_copies": stats.cow_copies,
           "wall_s": dt}
    if eng.allocators:
        row["pages_allocated"] = sum(a.total_allocated
                                     for a in eng.allocators)
    return row


def run_mode(mode, cfg, plan, mesh, params, sz):
    import jax
    from repro.configs.base import ShapeConfig
    from repro.core import steps
    from repro.serving import ServingEngine

    if mode == "contiguous":
        dshape = ShapeConfig("sb_d", "decode", sz["seq_budget"], sz["slots"])
        pshape = ShapeConfig("sb_p", "decode", sz["seq_budget"], 1)
        dec, _, _ = steps.make_decode_step(cfg, plan, mesh, dshape)
        pre, _, _ = steps.make_prefill_step(cfg, plan, mesh, pshape)
        eng = ServingEngine(cfg, plan, mesh, sz["slots"], sz["seq_budget"],
                            params, jax.jit(pre), jax.jit(dec))
    else:
        eng = ServingEngine.build_paged(
            cfg, plan, mesh, sz["slots"], sz["seq_budget"], params,
            page_size=sz["page_size"], prefill_chunk=sz["chunk"],
            prefix_cache=(mode == "prefix"))
    reqs = build_requests(sz, cfg.vocab_size)
    for r in reqs:
        eng.submit(r)
    t0 = time.perf_counter()
    stats = eng.run(max_ticks=50_000)
    dt = time.perf_counter() - t0
    assert all(r.done for r in reqs)
    row = _stats_row(mode, eng, stats, dt, sz["requests"])
    if mode == "prefix":
        # the whole point of the mode: the shared prefix is never recomputed
        assert stats.prefill_tokens_skipped > 0, \
            "prefix mode skipped no prefill tokens on a shared-prefix workload"
    return row


def run_priority_mode(mode, cfg, plan, mesh, params, sz):
    """Mixed-priority scenario: low-priority bulk saturates the slots, a
    high-priority burst lands mid-run.  mode: 'prio-fcfs' (baseline) or
    'prio-preempt' (PriorityScheduler with preemption).  -> (row, outputs)
    where outputs maps rid -> generated tokens (for cross-mode identity)."""
    import functools
    from repro.serving import PriorityScheduler, Request, ServingEngine

    scheduler = None
    if mode == "prio-preempt":
        scheduler = functools.partial(PriorityScheduler, preemption=True)
    # double-occupancy pool: enough slack that pages donated by preempted
    # requests survive (un-evicted) until the victims resume behind the
    # backlog — the KV-reuse signal this scenario reports
    n_pages = 2 * sz["slots"] * (sz["seq_budget"] // sz["page_size"]) + 1
    eng = ServingEngine.build_paged(
        cfg, plan, mesh, sz["slots"], sz["seq_budget"], params,
        page_size=sz["page_size"], prefill_chunk=sz["chunk"],
        n_pages=n_pages, prefix_cache=True, scheduler=scheduler)
    rng = np.random.RandomState(1)
    vocab = cfg.vocab_size
    low = [Request(rid=rid,
                   prompt=rng.randint(2, vocab, sz["prefix"]).astype(np.int32),
                   max_new_tokens=sz["max_new"], priority=0)
           for rid in range(sz["requests"])]
    high = [Request(rid=1000 + i,
                    prompt=rng.randint(2, vocab,
                                       sz["suffix"]).astype(np.int32),
                    max_new_tokens=sz["max_new"], priority=10)
            for i in range(max(2, sz["requests"] // 4))]
    for r in low:
        eng.submit(r)
    # land the burst once the first wave of prefills is decoding
    burst_at = -(-sz["prefix"] // sz["chunk"]) + 2
    t0 = time.perf_counter()
    tick = 0
    while eng.sched.has_pending() or \
            any(a is not None for a in eng.admissions):
        if tick == burst_at:
            for r in high:
                eng.submit(r)
        eng.tick()
        tick += 1
        assert tick < 50_000, "priority scenario did not converge"
    dt = time.perf_counter() - t0
    stats = eng.stats
    assert all(r.done for r in low + high)
    ttft = {cls: [stats.request_ttft[r.rid] for r in rs]
            for cls, rs in (("high", high), ("low", low))}
    row = _stats_row(mode, eng, stats, dt, len(low) + len(high))
    row["preemptions"] = stats.preemptions
    for cls in ("high", "low"):
        row[f"ttft_p50_ms_{cls}"] = float(np.median(ttft[cls])) * 1e3
        row[f"ttft_p99_ms_{cls}"] = float(np.percentile(ttft[cls], 99)) * 1e3
    outputs = {r.rid: tuple(r.out_tokens) for r in low + high}
    return row, outputs


def run_hybrid_mode(plan, mesh, sz):
    """Hybrid-arch (attention + SSM) paged serving row: the engine serves
    a reduced hymba config out of KV pages + recurrent-state slabs, and
    greedy outputs are asserted token-identical to the contiguous oracle
    (the acceptance bar for SSM slab paging).  -> row dict ("hybrid")."""
    import jax
    from repro.configs import get_config, reduced
    from repro.configs.base import ShapeConfig
    from repro.core import model, steps
    from repro.serving import Request, ServingEngine

    cfg = reduced(get_config("hymba-1.5b"), dtype="float32")
    params = model.init_params(cfg, plan)
    rng = np.random.RandomState(5)
    base = [(rng.randint(2, cfg.vocab_size,
                         int(rng.randint(4, sz["prefix"]))).astype(np.int32),
             sz["max_new"]) for _ in range(sz["requests"])]

    dshape = ShapeConfig("hb_d", "decode", sz["seq_budget"], sz["slots"])
    pshape = ShapeConfig("hb_p", "decode", sz["seq_budget"], 1)
    dec, _, _ = steps.make_decode_step(cfg, plan, mesh, dshape)
    pre, _, _ = steps.make_prefill_step(cfg, plan, mesh, pshape)
    oracle = ServingEngine(cfg, plan, mesh, sz["slots"], sz["seq_budget"],
                           params, jax.jit(pre), jax.jit(dec))
    refs = [Request(rid=i, prompt=p.copy(), max_new_tokens=m)
            for i, (p, m) in enumerate(base)]
    for r in refs:
        oracle.submit(r)
    oracle.run(max_ticks=50_000)
    ref_out = {r.rid: tuple(r.out_tokens) for r in refs}

    eng = ServingEngine.build_paged(
        cfg, plan, mesh, sz["slots"], sz["seq_budget"], params,
        page_size=sz["page_size"], prefill_chunk=sz["chunk"])
    reqs = [Request(rid=i, prompt=p.copy(), max_new_tokens=m)
            for i, (p, m) in enumerate(base)]
    for r in reqs:
        eng.submit(r)
    t0 = time.perf_counter()
    stats = eng.run(max_ticks=50_000)
    dt = time.perf_counter() - t0
    assert all(r.done for r in reqs)
    assert {r.rid: tuple(r.out_tokens) for r in reqs} == ref_out, \
        "hybrid paged outputs diverged from the contiguous oracle"
    # slab + page leak-freedom at completion
    a = eng.allocators[0]
    assert a.n_free == a.n_pages - a.n_reserved
    assert eng.slab_allocators[0].n_free == eng.n_slabs - 1
    return _stats_row("hybrid", eng, stats, dt, sz["requests"])


def run_spec_mode(cfg, plan, mesh, params, sz, k=4):
    """Speculative-decoding scenario: prompt-lookup drafts + k-token verify
    on a shared-prefix workload whose suffixes repeat a short motif (the
    traffic prompt lookup targets).  Greedy outputs are asserted
    token-identical to the non-speculative paged engine (the full
    policy/dp/sampling matrix lives in scripts/check_spec_identity.py) and
    the accepted-tokens rate feeds the regression gate.  -> row dict
    ("speculative")."""
    from repro.serving import Request, ServingEngine

    rng = np.random.RandomState(7)
    vocab = cfg.vocab_size
    shared = rng.randint(2, vocab, sz["prefix"]).astype(np.int32)
    base = []
    for i in range(sz["requests"]):
        motif = rng.randint(2, vocab, 3 + i % 3).astype(np.int32)
        body = np.tile(motif, 4)[: sz["suffix"] + i % 4]
        base.append(np.concatenate([shared, body]).astype(np.int32))
    max_new = 2 * sz["max_new"]   # room for repetition loops to develop
    # headroom pool so speculative page budgeting is never the bottleneck
    n_pages = 2 * sz["slots"] * (sz["seq_budget"] // sz["page_size"]) + 1

    outs = {}
    for spec in (0, k):
        eng = ServingEngine.build_paged(
            cfg, plan, mesh, sz["slots"], sz["seq_budget"], params,
            page_size=sz["page_size"], prefill_chunk=sz["chunk"],
            n_pages=n_pages, prefix_cache=True, speculative=spec)
        reqs = [Request(rid=i, prompt=p.copy(), max_new_tokens=max_new)
                for i, p in enumerate(base)]
        for r in reqs:
            eng.submit(r)
        t0 = time.perf_counter()
        stats = eng.run(max_ticks=50_000)
        dt = time.perf_counter() - t0
        assert all(r.done for r in reqs)
        outs[spec] = {r.rid: tuple(r.out_tokens) for r in reqs}
    assert outs[0] == outs[k], \
        "speculative outputs diverged from the one-token engine"
    row = _stats_row("speculative", eng, stats, dt, sz["requests"])
    row["speculative_k"] = k
    row["accepted_tokens_per_tick"] = stats.accepted_tokens_per_tick
    row["draft_hit_rate"] = stats.draft_hit_rate
    row["spec_accepted"] = stats.spec_accepted
    row["spec_drafted"] = stats.spec_drafted
    # the acceptance bar: speculation must beat one token per verify tick
    # on this workload, or the feature is dead weight
    assert row["accepted_tokens_per_tick"] > 1.0, \
        f"accepted_tokens_per_tick={row['accepted_tokens_per_tick']:.2f}"
    return row


def run_disagg_mode(cfg, plan, mesh, params, smoke=False):
    """Disaggregated-serving scenario: a decode-heavy background flood
    holds every page pool's full horizon while a burst of long-prefill
    interactive requests lands mid-run.  dp=2 interleaved admits the
    burst only as background requests retire (their pages are reserved
    through max_new), so burst TTFT rides the decode tail; dp=2
    ``disagg=(1, 1)`` budgets prompt-only pages on the prefill replica —
    the burst prefills immediately and its first tokens land before any
    decode capacity frees.  Greedy outputs are asserted token-identical
    across the two modes and the burst p99 TTFT improvement is the gated
    headline, with tokens/s within tolerance (the lock-step single-host
    loop executes both roles' compiled steps serially, so disagg pays the
    unbatched prefill rounds; the TTFT decoupling is the signal).

    The shape is fixed (same for --smoke and full): the pool exactly
    holds the whole background on one replica — equal decode width in
    both modes — while the interleaved per-replica slack stays below the
    burst's page horizon.  Compile time is excluded by a warm-up flood on
    each engine before the measured phase.  -> (rows, outputs) for modes
    dp2-interleaved / dp2-disagg."""
    from repro.serving import Request, ServingEngine

    SLOTS, N_PAGES, SEQ, CHUNK, PSZ = 4, 25, 112, 16, 8
    BG_N, BG_PROMPT, BG_NEW = 4, 4, 40      # 4 x 6 pages = the whole pool
    BU_N, BU_PROMPT, BU_NEW = 2, 96, 8      # 13-page horizon > 12 slack
    BURST_AT = 4

    def drive(disagg):
        eng = ServingEngine.build_paged(
            cfg, plan, mesh, SLOTS, SEQ, params, page_size=PSZ,
            prefill_chunk=CHUNK, n_pages=N_PAGES, dp=2, disagg=disagg)
        # warm-up: compile every step (including the committed-input
        # prefill entry) before the measured phase
        warm = [Request(rid=10_000 + i,
                        prompt=np.arange(2, CHUNK + 5).astype(np.int32) + i,
                        max_new_tokens=2) for i in range(4)]
        for r in warm:
            eng.submit(r)
        eng.run(max_ticks=50_000)
        h0, p0 = eng.stats.handoffs, eng.stats.pages_transferred
        rng = np.random.RandomState(9)
        vocab = cfg.vocab_size
        bg = [Request(rid=i, prompt=rng.randint(2, vocab, BG_PROMPT)
                      .astype(np.int32), max_new_tokens=BG_NEW)
              for i in range(BG_N)]
        bu = [Request(rid=100 + i, prompt=rng.randint(2, vocab, BU_PROMPT)
                      .astype(np.int32), max_new_tokens=BU_NEW)
              for i in range(BU_N)]
        t0 = time.perf_counter()
        for r in bg:
            eng.submit(r)
        tick = 0
        while eng.has_pending() or \
                any(a is not None for a in eng.admissions):
            if tick == BURST_AT:
                for r in bu:
                    eng.submit(r)
            eng.tick()
            tick += 1
            assert tick < 50_000, "disagg scenario did not converge"
        eng.drain()
        dt = time.perf_counter() - t0
        stats = eng.stats
        assert all(r.done for r in bg + bu)
        toks = sum(len(r.out_tokens) for r in bg + bu)
        ttft = [stats.request_ttft[r.rid] for r in bg + bu]
        ttft_bu = [stats.request_ttft[r.rid] for r in bu]
        row = {"mode": "dp2-disagg" if disagg else "dp2-interleaved",
               "requests": len(bg) + len(bu),
               "decoded_tokens": toks,
               "tokens_per_s": toks / dt,
               "ttft_p50_ms": float(np.median(ttft)) * 1e3,
               "ttft_p99_ms": float(np.percentile(ttft, 99)) * 1e3,
               "ttft_p99_ms_burst": float(np.percentile(ttft_bu, 99)) * 1e3,
               "handoffs": stats.handoffs - h0,
               "pages_transferred": stats.pages_transferred - p0,
               "wall_s": dt}
        return row, {r.rid: tuple(r.out_tokens) for r in bg + bu}

    int_row, int_out = drive(None)
    dis_row, dis_out = drive((1, 1))
    assert int_out == dis_out, "outputs changed under disaggregation"
    # every request prefilled on replica 0 and crossed exactly once
    assert dis_row["handoffs"] == BG_N + BU_N
    assert dis_row["pages_transferred"] > 0
    speedup = int_row["ttft_p99_ms_burst"] / \
        max(dis_row["ttft_p99_ms_burst"], 1e-9)
    tps_ratio = dis_row["tokens_per_s"] / max(int_row["tokens_per_s"], 1e-9)
    print(f"# disagg 1:1: burst p99 TTFT "
          f"interleaved={int_row['ttft_p99_ms_burst']:.1f}ms "
          f"disagg={dis_row['ttft_p99_ms_burst']:.1f}ms ({speedup:.2f}x) "
          f"tok/s ratio={tps_ratio:.2f} "
          f"({dis_row['handoffs']} handoffs, "
          f"{dis_row['pages_transferred']} pages transferred)")
    # the point of disaggregation: burst TTFT decouples from the decode
    # tail (observed ~2-2.6x; 1.2x leaves slack) at tokens/s within
    # tolerance (observed ~0.73-0.89x).  Smoke-noise handling mirrors the
    # priority gate: measured walls are tens of ms, so on shared CI
    # runners warn instead of flaking; full mode asserts hard.
    if speedup < 1.2 or tps_ratio < 0.6:
        msg = (f"disagg burst p99 speedup {speedup:.2f}x (< 1.2x) or "
               f"tok/s ratio {tps_ratio:.2f} (< 0.6)")
        assert smoke, msg
        print(f"::warning::{msg} — smoke wall-clock noise?")
    return [int_row, dis_row]


def run_elastic_mode(cfg, plan, mesh, params, sz, smoke=False):
    """Elastic-serving scenario: steady decode traffic on dp=2 loses a
    replica mid-run (``kill_replica`` — in-flight requests re-admitted on
    the survivor) and scales back to dp=2 a few ticks later (``scale_to``
    — the rejoin).  A second request wave lands right after the crash, so
    its TTFT prices the recovery window.  Reported against an undisturbed
    dp=2 run of the same traffic: ``recovered_throughput_ratio``
    (event-run tokens/s over baseline — how much of the fleet's
    throughput the membership churn costs end-to-end) and
    ``ttft_p99_ms_event`` (p99 TTFT of the post-crash arrivals).  Greedy
    outputs are asserted token-identical across the two runs — the
    membership changes must be invisible in the tokens.  Compile time is
    excluded by a discarded warm-up drive (which also compiles the dp=1
    step set the crash window runs on).  -> row dict ("elastic")."""
    from repro.serving import Request, ServingEngine

    KILL_AT, WAVE_AT, REJOIN_AT = 3, 4, 8
    max_new = 2 * sz["max_new"]

    def mk_reqs(seed):
        rng = np.random.RandomState(seed)
        vocab = cfg.vocab_size
        wave_a = [Request(rid=i, prompt=rng.randint(2, vocab, sz["suffix"])
                          .astype(np.int32), max_new_tokens=max_new)
                  for i in range(2 * sz["slots"])]
        wave_b = [Request(rid=100 + i, prompt=rng.randint(2, vocab,
                                                          sz["suffix"])
                          .astype(np.int32), max_new_tokens=max_new)
                  for i in range(sz["slots"])]
        return wave_a, wave_b

    def drive(with_event):
        eng = ServingEngine.build_paged(
            cfg, plan, mesh, sz["slots"], sz["seq_budget"], params,
            page_size=sz["page_size"], prefill_chunk=sz["chunk"],
            prefix_cache=True, dp=2)
        if with_event:
            pending = [(KILL_AT, "kill"), (REJOIN_AT, "scale")]

            def hook(e):
                while pending and e.stats.ticks >= pending[0][0]:
                    _, kind = pending.pop(0)
                    if kind == "kill":
                        e.kill_replica(1)
                    else:
                        e.scale_to(2)

            eng.membership_hook = hook
        wave_a, wave_b = mk_reqs(seed=13)
        t0 = time.perf_counter()
        for r in wave_a:
            eng.submit(r)
        tick = 0
        while eng.has_pending() or \
                any(a is not None for a in eng.admissions):
            if tick == WAVE_AT:
                for r in wave_b:
                    eng.submit(r)
            eng.tick()
            tick += 1
            assert tick < 50_000, "elastic scenario did not converge"
        dt = time.perf_counter() - t0
        reqs = wave_a + wave_b
        assert all(r.done for r in reqs)
        toks = sum(len(r.out_tokens) for r in reqs)
        ttft_ev = [eng.stats.request_ttft[r.rid] for r in wave_b]
        return eng, toks / dt, ttft_ev, dt, \
            {r.rid: tuple(r.out_tokens) for r in reqs}

    drive(True)                      # warm-up: compile dp=2 AND dp=1 sets
    _, base_tps, _, _, base_out = drive(False)
    eng, ev_tps, ttft_ev, dt, ev_out = drive(True)
    assert ev_out == base_out, "outputs changed under membership churn"
    st = eng.stats
    assert st.crashes == 1 and st.scale_events == 1
    assert st.readmitted > 0, "crash re-admitted no in-flight requests"
    ratio = ev_tps / max(base_tps, 1e-9)
    row = {"mode": "elastic",
           "requests": 3 * sz["slots"],
           "decoded_tokens": st.decoded_tokens,
           "tokens_per_s": ev_tps,
           "ttft_p99_ms_event": float(np.percentile(ttft_ev, 99)) * 1e3,
           "recovered_throughput_ratio": ratio,
           "crashes": st.crashes, "scale_events": st.scale_events,
           "migrations": st.migrations, "readmitted": st.readmitted,
           "wall_s": dt}
    print(f"# elastic: kill@{KILL_AT} rejoin@{REJOIN_AT}: "
          f"tok/s {ev_tps:.1f} vs baseline {base_tps:.1f} "
          f"(ratio {ratio:.2f}), post-crash p99 TTFT "
          f"{row['ttft_p99_ms_event']:.1f}ms, "
          f"{st.readmitted} re-admitted, {st.migrations} migrations")
    # the recovery bar: one crash + one rejoin must not halve the run's
    # throughput (observed ~0.7-0.95; 0.4 leaves slack for the re-prefill
    # work the crash forces).  Smoke walls are tens of ms on shared CI
    # runners — warn there, assert hard in full mode.
    if ratio < 0.4:
        msg = f"recovered throughput ratio {ratio:.2f} (< 0.4)"
        assert smoke, msg
        print(f"::warning::{msg} — smoke wall-clock noise?")
    return row


def _kv_pool_bytes(cfg, plan, n_pages, page_size):
    """Exact KV/cross pool footprint (payload + scale side tensors) from
    the cache template — what the engine would allocate, without building
    one."""
    from repro.core import kvcache
    from repro.core.partition import model_layout
    tmpl = kvcache.paged_cache_template(cfg, plan, model_layout(cfg, plan),
                                        n_pages, page_size)
    total = 0
    for pat in tmpl:
        for d in pat:
            for kind in ("kv", "cross"):
                for shape, dtype, _ in d.get(kind, {}).values():
                    total += int(np.prod(shape)) * np.dtype(dtype).itemsize
    return total


def run_quant_mode(cfg, plan_fp16, plan_i8, mesh, params, sz):
    """Quantized-KV scenario: int8 pools + per-page scales vs fp16 pools
    AT A FIXED POOL BYTE BUDGET.  The budget fits the fp16 pool exactly
    one request's pages; int8 pages cost ~half the bytes, so the same
    budget holds ~2x the pages and the engine admits strictly more
    requests concurrently — the capacity story behind quantizing at all.
    Reports pool bytes (ratio gated at <= 0.55x), tokens/s, and max
    concurrently admitted requests per variant.  -> row dict
    ("quant-int8")."""
    from repro.core.kvcache import pages_needed
    from repro.serving import ServingEngine

    need = pages_needed(sz["prefix"] + sz["suffix"] + sz["max_new"],
                        sz["page_size"])
    n_pages_fp16 = need + 1                      # budget: one admission
    budget = _kv_pool_bytes(cfg, plan_fp16, n_pages_fp16, sz["page_size"])
    per_page_i8 = _kv_pool_bytes(cfg, plan_i8, 2, sz["page_size"]) - \
        _kv_pool_bytes(cfg, plan_i8, 1, sz["page_size"])
    n_pages_i8 = budget // per_page_i8

    def drive(plan, n_pages):
        eng = ServingEngine.build_paged(
            cfg, plan, mesh, sz["slots"], sz["seq_budget"], params,
            page_size=sz["page_size"], prefill_chunk=sz["chunk"],
            n_pages=int(n_pages))
        reqs = build_requests(sz, cfg.vocab_size, seed=11)
        for r in reqs:
            eng.submit(r)
        t0 = time.perf_counter()
        tick, max_conc = 0, 0
        while eng.has_pending() or \
                any(a is not None for a in eng.admissions):
            eng.tick()
            tick += 1
            max_conc = max(max_conc,
                           sum(a is not None for a in eng.admissions))
            assert tick < 50_000, "quant scenario did not converge"
        dt = time.perf_counter() - t0
        assert all(r.done for r in reqs)
        return eng, eng.stats, dt, max_conc

    eng16, st16, dt16, conc16 = drive(plan_fp16, n_pages_fp16)
    eng8, st8, dt8, conc8 = drive(plan_i8, n_pages_i8)
    row = _stats_row("quant-int8", eng8, st8, dt8, sz["requests"])
    row["pool_bytes_fp16"] = budget
    row["pool_bytes_int8"] = _kv_pool_bytes(cfg, plan_i8, n_pages_fp16,
                                            sz["page_size"])
    row["bytes_ratio"] = row["pool_bytes_int8"] / budget
    row["n_pages_fp16"] = n_pages_fp16
    row["n_pages_int8"] = int(n_pages_i8)
    row["max_concurrent_fp16"] = conc16
    row["max_concurrent_int8"] = conc8
    row["tokens_per_s_fp16"] = st16.decoded_tokens / dt16
    # the two acceptance bars: int8 pages cost at most 0.55x the fp16
    # bytes, and the reclaimed budget buys real admission headroom
    assert row["bytes_ratio"] <= 0.55, \
        f"int8 pool bytes ratio {row['bytes_ratio']:.3f} > 0.55"
    assert conc8 > conc16, (conc8, conc16)
    return row


def run_dp_mode(dp, cfg, plan, mesh, params, sz):
    """dp-scaling scenario: two tenant groups, each sharing its own system
    prompt.  With dp=2 the router splits the tenants across replicas by
    prefix affinity, so each replica serves its tenant's prefix out of its
    own replica-local pool — per-replica hit rates stay high and greedy
    outputs are token-identical to the dp=1 oracle.  -> (row, outputs)."""
    from repro.serving import Request, ServingEngine
    eng = ServingEngine.build_paged(
        cfg, plan, mesh, sz["slots"], sz["seq_budget"], params,
        page_size=sz["page_size"], prefill_chunk=sz["chunk"],
        prefix_cache=True, dp=dp)
    rng = np.random.RandomState(3)
    vocab = cfg.vocab_size
    tenants = [rng.randint(2, vocab, sz["prefix"]).astype(np.int32)
               for _ in range(2)]
    reqs = []
    for rid in range(2 * sz["requests"]):
        suf = rng.randint(2, vocab, sz["suffix"]).astype(np.int32)
        reqs.append(Request(
            rid=rid, prompt=np.concatenate([tenants[rid % 2], suf]),
            max_new_tokens=sz["max_new"]))
    for r in reqs:
        eng.submit(r)
    t0 = time.perf_counter()
    stats = eng.run(max_ticks=50_000)
    dt = time.perf_counter() - t0
    assert all(r.done for r in reqs)
    row = _stats_row(f"dp{dp}", eng, stats, dt, len(reqs))
    row["dp"] = dp
    row["affinity_routed"] = eng.router.affinity_routed
    for rr, rs in enumerate(stats.replicas):
        row[f"prefix_hit_rate_r{rr}"] = rs.prefix_hit_rate
        row[f"routed_r{rr}"] = rs.routed
    # per-replica leak-freedom: every page free or cache-held after the run
    for rr in range(dp):
        a, c = eng.allocators[rr], eng.prefix_caches[rr]
        assert a.n_free + c.n_cached_pages == a.n_pages - a.n_reserved, rr
    return row, {r.rid: tuple(r.out_tokens) for r in reqs}


def rows(smoke: bool = False):
    import jax
    from repro.configs import get_config, reduced
    from repro.core import model
    from repro.core.partition import ShardingPlan
    from repro.launch.mesh import make_mesh

    sz = SIZES["smoke" if smoke else "full"]
    cfg = reduced(get_config("tinyllama-42m"), dtype="float32")
    plan = ShardingPlan(tp=1, kv_cache_dtype="float32")
    mesh = make_mesh((1, 1), ("data", "model"), devices=jax.devices()[:1])
    params = model.init_params(cfg, plan)
    out = [run_mode(m, cfg, plan, mesh, params, sz)
           for m in ("contiguous", "paged", "prefix")]
    fcfs_row, fcfs_out = run_priority_mode("prio-fcfs", cfg, plan, mesh,
                                           params, sz)
    pre_row, pre_out = run_priority_mode("prio-preempt", cfg, plan, mesh,
                                         params, sz)
    # schedule-invariance: greedy outputs are identical under both policies
    # even though prio-preempt evicted and resumed low-priority requests
    assert fcfs_out == pre_out, "outputs changed under preemptive scheduling"
    speedup = fcfs_row["ttft_p99_ms_high"] / max(pre_row["ttft_p99_ms_high"],
                                                 1e-9)
    print(f"# high-priority p99 TTFT: fcfs={fcfs_row['ttft_p99_ms_high']:.1f}"
          f"ms preempt={pre_row['ttft_p99_ms_high']:.1f}ms "
          f"({speedup:.1f}x, {pre_row['preemptions']} preemptions)")
    # the QoS point of the policy: urgent arrivals must not queue behind
    # bulk work (observed ~8-10x; 2x leaves slack for noise).  Smoke-shape
    # TTFTs are single-digit ms over ~2 samples, so on shared CI runners
    # one scheduler stall could flake the ratio — warn there and leave the
    # trend to check_regression; full mode asserts hard.
    if speedup < 2.0:
        msg = f"priority preemption gained only {speedup:.2f}x (< 2x)"
        assert smoke, msg
        print(f"::warning::{msg} — smoke wall-clock noise?")
    assert pre_row["preemptions"] > 0
    # ...and the victims' KV was reused on resume, not recomputed
    assert pre_row["prefill_tokens_skipped"] > 0
    # dp scaling: replica-sharded pools + prefix-affinity routing
    dp1_row, dp1_out = run_dp_mode(1, cfg, plan, mesh, params, sz)
    dp2_row, dp2_out = run_dp_mode(2, cfg, plan, mesh, params, sz)
    assert dp1_out == dp2_out, "outputs changed under dp=2 routing"
    # each replica owns one tenant's prefix: both hit rates are nonzero
    assert dp2_row["routed_r0"] > 0 and dp2_row["routed_r1"] > 0
    assert dp2_row["prefix_hit_rate_r0"] > 0
    assert dp2_row["prefix_hit_rate_r1"] > 0
    print(f"# dp scaling: dp1={dp1_row['tokens_per_s']:.1f} tok/s "
          f"dp2={dp2_row['tokens_per_s']:.1f} tok/s "
          f"(replica hit rates {dp2_row['prefix_hit_rate_r0']:.2f}/"
          f"{dp2_row['prefix_hit_rate_r1']:.2f}, "
          f"{dp2_row['affinity_routed']} affinity-routed)")
    # hybrid (attention + SSM slabs) paged serving, oracle-checked
    hybrid_row = run_hybrid_mode(plan, mesh, sz)
    print(f"# hybrid arch: {hybrid_row['tokens_per_s']:.1f} tok/s "
          f"(outputs oracle-identical, slabs leak-free)")
    # speculative decoding: prompt-lookup drafts, identity-checked
    spec_row = run_spec_mode(cfg, plan, mesh, params, sz)
    print(f"# speculative k={spec_row['speculative_k']}: "
          f"accepted_tokens_per_tick="
          f"{spec_row['accepted_tokens_per_tick']:.2f} "
          f"draft_hit_rate={spec_row['draft_hit_rate']:.2f} "
          f"({spec_row['spec_accepted']}/{spec_row['spec_drafted']} "
          f"draft tokens accepted; outputs identical to one-token engine)")
    # quantized pools: int8 vs fp16 at a fixed pool byte budget
    quant_row = run_quant_mode(
        cfg, ShardingPlan(tp=1, kv_cache_dtype="bfloat16"),
        ShardingPlan(tp=1, kv_cache_dtype="int8"), mesh, params, sz)
    print(f"# quantized KV: int8 pool bytes "
          f"{quant_row['bytes_ratio']:.3f}x fp16, max concurrent "
          f"{quant_row['max_concurrent_int8']} vs "
          f"{quant_row['max_concurrent_fp16']} at the same byte budget "
          f"({quant_row['n_pages_int8']} vs {quant_row['n_pages_fp16']} "
          f"pages)")
    # disaggregated prefill/decode: burst TTFT decoupling, oracle-checked
    disagg_rows = run_disagg_mode(cfg, plan, mesh, params, smoke=smoke)
    # elastic membership: crash + rejoin under load, identity-checked
    elastic_row = run_elastic_mode(cfg, plan, mesh, params, sz, smoke=smoke)
    return out + [fcfs_row, pre_row, dp1_row, dp2_row, hybrid_row, spec_row,
                  quant_row] + disagg_rows + [elastic_row]


def main(smoke=False, json_path=None):
    import jax
    out = rows(smoke=smoke)
    keys = list(dict.fromkeys(k for r in out for k in r))
    print(",".join(keys))
    for r in out:
        print(",".join(f"{r.get(k):.4g}" if isinstance(r.get(k), float)
                       else str(r.get(k, "")) for k in keys))
    if json_path:
        payload = {"bench": "serving", "mode": "smoke" if smoke else "full",
                   "unix_time": time.time(), "jax": jax.__version__,
                   "rows": out}
        with open(json_path, "w") as f:
            json.dump(payload, f, indent=2)
        print(f"# wrote {json_path}")
    return out


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny shapes (CI bench-smoke job)")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write rows to a BENCH_*.json artifact")
    a = ap.parse_args()
    main(smoke=a.smoke, json_path=a.json)
