"""What the serving program records about itself: a name for every
compiled step, the tick's host phases as profiler spans and
``EngineStats`` counters, and the admission stamp on each request."""
import time

import jax
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.core import model, steps
from repro.core.partition import ShardingPlan
from repro.serving import Request, SamplerConfig, ServingEngine
from repro.serving.engine import PHASE_COUNTERS

PLAN = ShardingPlan(tp=1, kv_cache_dtype="float32")
PSZ, N_MAX, CHUNK, SLOTS, REPS, DRAFT = 8, 4, 8, 2, 2, 2
N_PAGES = SLOTS * N_MAX + 1


def _lowered(jitted, *args) -> str:
    return jitted.lower(*args).as_text()


def _args(t, names):
    return [t[k] for k in names]


def test_paged_step_modules_carry_step_names(mesh1):
    """Each step of the paged step set lowers to a module named after the
    step, not after the shared per-shard wrapper."""
    cfg = reduced(get_config("tinyllama-42m"), dtype="float32")
    params = model.abstract_params(cfg, PLAN)
    sset = steps.paged_step_set(cfg, PLAN, mesh1, SLOTS, N_PAGES, PSZ, N_MAX,
                                CHUNK, n_replicas=REPS, speculative=DRAFT)
    _, dt, _ = steps.make_paged_decode_step(cfg, PLAN, mesh1, SLOTS, N_PAGES,
                                            PSZ, N_MAX, n_replicas=REPS)
    _, pt, _ = steps.make_prefill_chunk_step(cfg, PLAN, mesh1, CHUNK, N_PAGES,
                                             PSZ, N_MAX, n_replicas=REPS)
    _, ct, _ = steps.make_page_copy_step(cfg, PLAN, mesh1, N_PAGES, PSZ,
                                         n_replicas=REPS)
    _, vt, _ = steps.make_verify_step(cfg, PLAN, mesh1, SLOTS, DRAFT + 1,
                                      N_PAGES, PSZ, N_MAX, n_replicas=REPS)
    _, tt, _ = steps.make_page_transfer_step(cfg, PLAN, mesh1, N_PAGES, PSZ,
                                             N_MAX, n_replicas=REPS)
    ecfg = reduced(get_config("seamless-m4t-large-v2"), dtype="float32",
                   n_enc_layers=1, enc_seq_len=16)
    eset = steps.paged_step_set(ecfg, PLAN, mesh1, SLOTS, N_PAGES, PSZ,
                                N_MAX, CHUNK)
    _, wt, _ = steps.make_cross_kv_write_step(ecfg, PLAN, mesh1, N_PAGES, PSZ)
    with mesh1:
        texts = {
            "jit_paged_decode": _lowered(
                sset["decode"], params, dt["cache"],
                *_args(dt, ("tokens1", "pos", "block_table"))),
            "jit_prefill_chunk": _lowered(
                sset["prefill"], params, pt["cache"],
                *_args(pt, ("tokens", "chunk_start", "last_idx",
                            "block_table"))),
            "jit_page_copy": _lowered(sset["copy"], ct["cache"], ct["src"],
                                      ct["dst"]),
            "jit_verify": _lowered(
                sset["verify"], params, vt["cache"],
                *_args(vt, ("tokens", "pos", "qlen", "block_table"))),
            "jit_page_transfer": _lowered(
                sset["transfer"], tt["cache"],
                *_args(tt, ("src_rep", "dst_rep", "src_pages",
                            "dst_pages"))),
            "jit_cross_kv_write": _lowered(
                eset["cross_write"], model.abstract_params(ecfg, PLAN),
                wt["cache"], wt["frames"], wt["cross_bt"]),
            "jit_zero_slab": _lowered(
                steps.zero_slab,
                jax.ShapeDtypeStruct((2, REPS, 3, 4), np.float32), 1, 2),
        }
    for name, text in texts.items():
        assert f"module @{name} " in text, name
        assert "per_shard" not in text, name


def _engine(cfg, params, mesh, slots=2, sampler=None, prefix_cache=False):
    return ServingEngine.build_paged(cfg, PLAN, mesh, slots, 64, params,
                                     page_size=8, prefill_chunk=8,
                                     sampler=sampler,
                                     prefix_cache=prefix_cache)


def _tick_until_done(eng, reqs, max_ticks=500) -> float:
    """Tick until every request is done; -> host seconds inside tick()."""
    wall = 0.0
    for _ in range(max_ticks):
        if all(r.done for r in reqs):
            break
        t0 = time.perf_counter()
        eng.tick()
        wall += time.perf_counter() - t0
    assert all(r.done for r in reqs)
    return wall


@pytest.fixture(scope="module")
def tiny():
    cfg = reduced(get_config("tinyllama-42m"), dtype="float32")
    return cfg, model.init_params(cfg, PLAN)


def test_tick_phases_and_request_stamps(mesh1, tiny):
    """A sampled and a greedy request: every stamp is in order, each host
    phase is timed, and the phases fit inside the ticks' wall time; the
    sampler the instance carries (here replaced for the greedy row) is
    timed at its call site."""
    cfg, params = tiny
    eng = _engine(cfg, params, mesh1,
                  sampler=SamplerConfig(temperature=0.8, top_k=20))
    own = eng._sample_row
    calls = []

    def sample_row(logits, b, req):
        calls.append(req.rid)
        if req.rid == 1:
            return int(np.argmax(logits[b]))
        return own(logits, b, req)

    eng._sample_row = sample_row
    rng = np.random.RandomState(3)
    reqs = [Request(rid=i, prompt=rng.randint(2, cfg.vocab_size, L)
                    .astype(np.int32), max_new_tokens=m)
            for i, (L, m) in enumerate([(13, 5), (9, 4)])]
    for r in reqs:
        eng.submit(r)
    wall = _tick_until_done(eng, reqs)
    for r in reqs:
        assert r.t_submit <= r.t_admitted <= r.t_first_token <= r.t_done
    assert sorted(set(calls)) == [0, 1]
    st = eng.stats
    phases = [getattr(st, f) for f in PHASE_COUNTERS.values()]
    assert all(p > 0 for p in phases), dict(zip(PHASE_COUNTERS, phases,
                                                strict=True))
    assert sum(phases) <= wall


def test_phase_spans_land_in_a_profiler_trace(mesh1, tiny, tmp_path):
    """With the profiler on, each phase is a host span of its own name."""
    from jax.profiler import ProfileData
    cfg, params = tiny
    eng = _engine(cfg, params, mesh1)
    reqs = [Request(rid=0, prompt=np.arange(2, 14, dtype=np.int32),
                    max_new_tokens=3)]
    eng.submit(reqs[0])
    eng.tick()                     # compile outside the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        _tick_until_done(eng, reqs)
    finally:
        jax.profiler.stop_trace()
    found = sorted(tmp_path.rglob("*.xplane.pb"))
    assert found
    pd = ProfileData.from_serialized_xspace(found[-1].read_bytes())
    names = {ev.name for p in pd.planes if p.name.startswith("/host:")
             for line in p.lines for ev in line.events}
    assert set(PHASE_COUNTERS) <= names


def test_preempted_request_keeps_first_admission_stamp(mesh1, tiny):
    """A request preempted mid-decode and resumed later keeps the stamp
    of its first admission."""
    cfg, params = tiny
    eng = _engine(cfg, params, mesh1, slots=1, prefix_cache=True)
    rng = np.random.RandomState(5)
    r1 = Request(rid=0, prompt=rng.randint(2, cfg.vocab_size, 12)
                 .astype(np.int32), max_new_tokens=8)
    r2 = Request(rid=1, prompt=rng.randint(2, cfg.vocab_size, 10)
                 .astype(np.int32), max_new_tokens=3)
    eng.submit(r1)
    eng.submit(r2)
    for _ in range(200):
        if len(r1.out_tokens) >= 4:
            break
        eng.tick()
    assert eng.admissions[0].req is r1 and not r1.done
    first = r1.t_admitted
    assert first is not None
    eng.preempt(0)
    eng.run(max_ticks=500)
    assert r1.done and r2.done and eng.stats.preemptions == 1
    assert r1.t_admitted == first
    assert r2.t_admitted > first
