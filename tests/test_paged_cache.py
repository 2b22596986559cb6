"""Paged KV-cache subsystem: allocator behavior, paged-vs-contiguous
equivalence (attention level, step level, engine level) and engine
admission/eviction under a randomized request mix."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from greedy_ref import greedy_reference

from repro.configs import get_config, reduced
from repro.configs.base import ShapeConfig
from repro.core import model, steps
from repro.core.kvcache import PageAllocator, pages_needed
from repro.core.partition import ShardingPlan

PLAN = ShardingPlan(tp=1, kv_cache_dtype="float32")


# ---------------------------------------------------------------------------
# allocator
# ---------------------------------------------------------------------------

def test_page_allocator_reuse_and_exhaustion():
    a = PageAllocator(8)                 # page 0 reserved -> 7 usable
    assert a.n_free == 7
    p1 = a.alloc(3)
    p2 = a.alloc(4)
    assert sorted(p1 + p2) == list(range(1, 8))
    assert a.alloc(1) is None            # exhausted: all-or-nothing
    assert a.n_free == 0
    a.free(p1)
    assert a.n_free == 3
    p3 = a.alloc(3)
    assert sorted(p3) == sorted(p1)      # freed pages are reused
    with pytest.raises(AssertionError):
        a.free([0])                      # the scratch page is never freed
    a.free(p3)
    with pytest.raises(AssertionError):
        a.free(p3)                       # double free


def test_pages_needed():
    assert pages_needed(1, 8) == 1
    assert pages_needed(8, 8) == 1
    assert pages_needed(9, 8) == 2


# ---------------------------------------------------------------------------
# attention level: gather path and Pallas kernel vs contiguous oracle
# ---------------------------------------------------------------------------

def _scatter_to_pages(k, bt, psz, n_pages):
    """Contiguous (B, G, S, D) -> head-major page pool (n_pages, G, psz,
    D), the Pallas kernels' layout."""
    B, G, S, D = k.shape
    pool = np.zeros((n_pages, G, psz, D), k.dtype)
    for b in range(B):
        for t in range(S):
            pool[bt[b, t // psz], :, t % psz] = k[b, :, t]
    return pool


def _token_major(pool):
    """Head-major pages (n_pages, G, psz, D) -> the served pools' token-
    major pages (n_pages, psz, G, D)."""
    return np.ascontiguousarray(pool.transpose(0, 2, 1, 3))


def _random_tables(rng, B, n_max, n_pages):
    ids = rng.permutation(np.arange(1, n_pages))[: B * n_max]
    return ids.reshape(B, n_max).astype(np.int32)


def test_paged_decode_attention_matches_contiguous():
    from repro.core.attention import decode_attention, paged_decode_attention
    rng = np.random.RandomState(0)
    B, G, R, D, psz, n_max = 3, 2, 2, 16, 4, 6
    n_pages = B * n_max + 1
    S = n_max * psz
    lens = np.array([5, 24, 17], np.int32)
    q = rng.randn(B, G, R, D).astype(np.float32)
    k = rng.randn(B, G, S, D).astype(np.float32)
    v = rng.randn(B, G, S, D).astype(np.float32)
    bt = _random_tables(rng, B, n_max, n_pages)
    kp = _token_major(_scatter_to_pages(k, bt, psz, n_pages))
    vp = _token_major(_scatter_to_pages(v, bt, psz, n_pages))
    kv_pos = np.broadcast_to(np.arange(S, dtype=np.int32), (B, S))
    for window in (0, 7):
        ref = decode_attention(jnp.asarray(q), jnp.asarray(k),
                               jnp.asarray(v), jnp.asarray(kv_pos),
                               jnp.asarray(lens), window=window)
        got = paged_decode_attention(jnp.asarray(q), jnp.asarray(kp),
                                     jnp.asarray(vp), jnp.asarray(bt),
                                     jnp.asarray(lens), window=window)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)


def test_pallas_paged_decode_kernel():
    from repro.kernels import ref
    from repro.kernels.decode_attention import paged_decode_attention
    rng = np.random.RandomState(1)
    B, H, D, psz, n_max = 3, 4, 64, 8, 5
    n_pages = B * n_max + 1
    S = n_max * psz
    lens = np.array([13, 40, 1], np.int32)
    q = rng.randn(B, H, D).astype(np.float32)
    k = rng.randn(B, H, S, D).astype(np.float32)
    v = rng.randn(B, H, S, D).astype(np.float32)
    bt = _random_tables(rng, B, n_max, n_pages)
    kp = _scatter_to_pages(k, bt, psz, n_pages)
    vp = _scatter_to_pages(v, bt, psz, n_pages)
    out = paged_decode_attention(jnp.asarray(q), jnp.asarray(kp),
                                 jnp.asarray(vp), jnp.asarray(bt),
                                 jnp.asarray(lens), interpret=True)
    expect = ref.ref_decode_attention(jnp.asarray(q), jnp.asarray(k),
                                      jnp.asarray(v), jnp.asarray(lens))
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=1e-4, atol=1e-4)


def test_paged_verify_attention_matches_ref():
    """Pure-JAX Q-query verify attention (the engine's spec path) against
    the ref oracle; cur_pos semantics (pos of query 0) vs the oracle's
    length = pos + 1."""
    from repro.core.attention import paged_verify_attention
    from repro.kernels import ref
    rng = np.random.RandomState(2)
    B, G, R, Q, D, psz, n_max = 3, 2, 2, 5, 16, 4, 8
    n_pages = B * n_max + 1
    S = n_max * psz
    pos = np.array([4, 19, 27], np.int32)    # query 0's absolute position
    q = rng.randn(B, G, R, Q, D).astype(np.float32)
    k = rng.randn(B, G, S, D).astype(np.float32)
    v = rng.randn(B, G, S, D).astype(np.float32)
    bt = _random_tables(rng, B, n_max, n_pages)
    kp = _token_major(_scatter_to_pages(k, bt, psz, n_pages))
    vp = _token_major(_scatter_to_pages(v, bt, psz, n_pages))
    got = paged_verify_attention(jnp.asarray(q), jnp.asarray(kp),
                                 jnp.asarray(vp), jnp.asarray(bt),
                                 jnp.asarray(pos))
    # fold (G, R) -> H for the ref oracle's (B, H, Q, D) layout
    qh = q.reshape(B, G * R, Q, D)
    kh = np.repeat(k, R, axis=1)
    vh = np.repeat(v, R, axis=1)
    expect = ref.ref_verify_attention(jnp.asarray(qh), jnp.asarray(kh),
                                      jnp.asarray(vh),
                                      jnp.asarray(pos + 1))
    np.testing.assert_allclose(
        np.asarray(got).reshape(B, G * R, Q, D), np.asarray(expect),
        rtol=1e-5, atol=1e-5)


def test_pallas_paged_verify_kernel():
    from repro.kernels import ref
    from repro.kernels.decode_attention import paged_verify_attention
    rng = np.random.RandomState(3)
    B, H, Q, D, psz, n_max = 3, 4, 5, 64, 8, 5
    n_pages = B * n_max + 1
    S = n_max * psz
    lens = np.array([13, 36, 1], np.int32)   # pos + 1, as in paged decode
    q = rng.randn(B, H, Q, D).astype(np.float32)
    k = rng.randn(B, H, S, D).astype(np.float32)
    v = rng.randn(B, H, S, D).astype(np.float32)
    bt = _random_tables(rng, B, n_max, n_pages)
    kp = _scatter_to_pages(k, bt, psz, n_pages)
    vp = _scatter_to_pages(v, bt, psz, n_pages)
    out = paged_verify_attention(jnp.asarray(q), jnp.asarray(kp),
                                 jnp.asarray(vp), jnp.asarray(bt),
                                 jnp.asarray(lens), interpret=True)
    expect = ref.ref_verify_attention(jnp.asarray(q), jnp.asarray(k),
                                      jnp.asarray(v), jnp.asarray(lens))
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=1e-4, atol=1e-4)
    # Q = 1 degenerates to the decode kernel's contract
    out1 = paged_verify_attention(jnp.asarray(q[:, :, :1]), jnp.asarray(kp),
                                  jnp.asarray(vp), jnp.asarray(bt),
                                  jnp.asarray(lens), interpret=True)
    exp1 = ref.ref_decode_attention(jnp.asarray(q[:, :, 0]), jnp.asarray(k),
                                    jnp.asarray(v), jnp.asarray(lens))
    np.testing.assert_allclose(np.asarray(out1[:, :, 0]), np.asarray(exp1),
                               rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# step level: chunked prefill + paged decode == exact-length prefill + decode
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_paged_steps_match_contiguous_mixed_lengths(mesh1):
    cfg = reduced(get_config("qwen3-0.6b"), dtype="float32")
    params = model.init_params(cfg, PLAN)
    rng = np.random.RandomState(0)
    S, PSZ, CHUNK, NDEC = 32, 4, 8, 4
    N_MAX = S // PSZ
    N_PAGES = 3 * N_MAX + 1

    dec, _, _ = steps.make_decode_step(cfg, PLAN, mesh1,
                                       ShapeConfig("d", "decode", S, 1))
    dec = jax.jit(dec)
    chunk_fn, _, _ = steps.make_prefill_chunk_step(cfg, PLAN, mesh1, CHUNK,
                                                   N_PAGES, PSZ, N_MAX)
    pdec, _, _ = steps.make_paged_decode_step(cfg, PLAN, mesh1, 1, N_PAGES,
                                              PSZ, N_MAX)
    chunk_fn, pdec = jax.jit(chunk_fn), jax.jit(pdec)
    alloc = PageAllocator(N_PAGES)

    for L in (5, 13, 26):                # mixed prompt lengths, one compile
        prompt = rng.randint(2, cfg.vocab_size, L).astype(np.int32)

        # contiguous reference (compiles per length — the cost paging kills)
        pre, _, _ = steps.make_prefill_step(cfg, PLAN, mesh1,
                                            ShapeConfig("p", "decode", S, 1))
        cache = steps.zero_cache_for(cfg, PLAN, mesh1, 1, S)
        with mesh1:
            lg, cache = jax.jit(pre)(params, jnp.asarray(prompt[None]), cache)
        ref_logits = [np.asarray(lg[0], np.float64)]
        tok, pos = int(np.argmax(ref_logits[-1])), L
        with mesh1:
            for _ in range(NDEC):
                lg, cache = dec(params, cache,
                                jnp.asarray([[tok]], jnp.int32),
                                jnp.asarray([pos], jnp.int32))
                ref_logits.append(np.asarray(lg[0], np.float64))
                tok, pos = int(np.argmax(ref_logits[-1])), pos + 1

        # paged: chunk-at-a-time prefill, then block-table decode
        pcache = steps.zero_paged_cache_for(cfg, PLAN, mesh1, N_PAGES, PSZ)
        pages = alloc.alloc(pages_needed(L + NDEC, PSZ))
        bt = np.zeros((1, N_MAX), np.int32)
        bt[0, :len(pages)] = pages
        n_chunks = -(-L // CHUNK)
        padded = np.zeros(n_chunks * CHUNK, np.int32)
        padded[:L] = prompt
        with mesh1:
            for c0 in range(0, n_chunks * CHUNK, CHUNK):
                lg, pcache = chunk_fn(
                    params, pcache, jnp.asarray(padded[None, c0:c0 + CHUNK]),
                    jnp.asarray([c0], jnp.int32),
                    jnp.asarray([min(L - 1 - c0, CHUNK - 1)], jnp.int32),
                    jnp.asarray(bt))
        got_logits = [np.asarray(lg[0], np.float64)]
        tok, pos = int(np.argmax(got_logits[-1])), L
        with mesh1:
            for _ in range(NDEC):
                lg, pcache = pdec(params, pcache,
                                  jnp.asarray([[tok]], jnp.int32),
                                  jnp.asarray([pos], jnp.int32),
                                  jnp.asarray(bt))
                got_logits.append(np.asarray(lg[0], np.float64))
                tok, pos = int(np.argmax(got_logits[-1])), pos + 1

        np.testing.assert_allclose(np.asarray(got_logits),
                                   np.asarray(ref_logits), atol=1e-5)
        alloc.free(pages)


def _changed_rows(before, after, n_lead):
    """-> set of index tuples over the first ``n_lead`` axes of a leaf
    where ``after`` differs from ``before`` anywhere in the trailing
    axes (bitwise)."""
    diff = before != after
    diff = diff.reshape(diff.shape[:n_lead] + (-1,)).any(-1)
    return set(map(tuple, np.argwhere(diff).tolist()))


@pytest.mark.parametrize("name", ["qwen3-0.6b", "mamba2-370m"])
def test_paged_steps_write_only_addressed_rows(name, mesh1):
    """One prefill chunk and one decode step update the carried pools in
    place: every layer's (page, offset) rows and slab entries that the
    step addresses change, and every other layer, page, offset and slab
    stays bit-identical.  The logits are the contiguous reference path's
    (head-major caches, never paged)."""
    cfg = reduced(get_config(name), dtype="float32")
    params = model.init_params(cfg, PLAN)
    has_ssm = steps.paged_extra_inputs(cfg)[0]
    S, PSZ, CHUNK, L = 32, 4, 16, 11
    N_MAX, N_PAGES, N_SLABS = S // PSZ, 2 * (S // PSZ) + 1, 3
    bt = np.zeros((2, N_MAX), np.int32)          # row 1: idle, scratch page
    bt[0] = np.arange(N_MAX) * 2 + 3             # pages 3, 5, ... 17
    sid = 2
    prompt = np.random.RandomState(0).randint(2, cfg.vocab_size, L) \
        .astype(np.int32)

    # reference: exact-length contiguous prefill, then one decode step
    pre, _, _ = steps.make_prefill_step(cfg, PLAN, mesh1,
                                        ShapeConfig("p", "decode", S, 1))
    dec, _, _ = steps.make_decode_step(cfg, PLAN, mesh1,
                                       ShapeConfig("d", "decode", S, 1))
    with mesh1:
        ref_pre, cc = jax.jit(pre)(params, jnp.asarray(prompt[None]),
                                   steps.zero_cache_for(cfg, PLAN, mesh1, 1,
                                                        S))
        tok = int(np.argmax(ref_pre[0]))
        ref_dec, _ = jax.jit(dec)(params, cc, jnp.asarray([[tok]], jnp.int32),
                                  jnp.asarray([L], jnp.int32))

    # paged: every pool filled with noise, the request's slab zeroed as the
    # engine zeroes a fresh one
    chunk_fn, _, _ = steps.make_prefill_chunk_step(
        cfg, PLAN, mesh1, CHUNK, N_PAGES, PSZ, N_MAX,
        n_slabs=N_SLABS if has_ssm else 0)
    pdec, _, _ = steps.make_paged_decode_step(
        cfg, PLAN, mesh1, 2, N_PAGES, PSZ, N_MAX,
        n_slabs=N_SLABS if has_ssm else 0)
    cache = steps.zero_paged_cache_for(cfg, PLAN, mesh1, N_PAGES, PSZ,
                                       n_slabs=N_SLABS if has_ssm else 0)
    rng = np.random.RandomState(1)
    cache = jax.tree_util.tree_map(
        lambda x: jnp.asarray(rng.randn(*x.shape), x.dtype), cache)
    cache = [[{k: (jax.tree_util.tree_map(
        lambda v: steps.zero_slab(v, 0, sid), sub) if k == "ssm" else sub)
        for k, sub in d.items()} for d in pat] for pat in cache]
    toks = np.zeros((1, CHUNK), np.int32)
    toks[0, :L] = prompt
    slab_in = [jnp.asarray([sid], jnp.int32)] if has_ssm else []
    with mesh1:
        got_pre, c1 = jax.jit(chunk_fn)(
            params, cache, jnp.asarray(toks), jnp.asarray([0], jnp.int32),
            jnp.asarray([L - 1], jnp.int32), jnp.asarray(bt[:1]), *slab_in)
        slab_in = [jnp.asarray([sid, 0], jnp.int32)] if has_ssm else []
        got_dec, c2 = jax.jit(pdec)(
            params, c1, jnp.asarray([[tok], [0]], jnp.int32),
            jnp.asarray([L, 0], jnp.int32), jnp.asarray(bt), *slab_in)
    np.testing.assert_allclose(np.asarray(got_pre[0]), np.asarray(ref_pre[0]),
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(got_dec[0]), np.asarray(ref_dec[0]),
                               atol=1e-5)

    # (page, offset) rows each step addresses: the chunk writes all CHUNK
    # positions of row 0; decode writes position L of row 0 and position 0
    # of the idle row, which points at the scratch page and slab.  The live
    # request's own rows (prompt positions, then position L) must change.
    def page_of(t):
        return int(bt[0, t // PSZ]), t % PSZ

    chunk_rows = {page_of(t) for t in range(CHUNK)}
    dec_rows = {page_of(L), (0, 0)}
    for before, after, rows, live_rows, slabs in (
            (cache, c1, chunk_rows, {page_of(t) for t in range(L)}, {sid}),
            (c1, c2, dec_rows, {page_of(L)}, {sid, 0})):
        for pat_b, pat_a in zip(before, after, strict=True):
            for d_b, d_a in zip(pat_b, pat_a, strict=True):
                assert d_b.keys() == d_a.keys()
                for kind in d_b:
                    for key, leaf in d_b[kind].items():
                        b, a = np.asarray(leaf), np.asarray(d_a[kind][key])
                        layers = range(b.shape[0])
                        if kind == "kv":     # (reps, R, n_pages, psz, G, D)
                            want = {(i, 0) + r for i in layers for r in rows}
                            live = {(i, 0) + r for i in layers
                                    for r in live_rows}
                            got = _changed_rows(b, a, 4)
                        else:                # (reps, R, n_slabs, ...)
                            want = {(i, 0, j) for i in layers for j in slabs}
                            live = {(i, 0, sid) for i in layers}
                            got = _changed_rows(b, a, 3)
                        assert got <= want, (kind, key, sorted(got - want))
                        assert live <= got, (kind, key, sorted(live - got))


# ---------------------------------------------------------------------------
# engine level: randomized workload, admission under page pressure
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_paged_engine_matches_contiguous_greedy(mesh1):
    """Greedy outputs are token-identical to the contiguous steps' greedy
    reference (``greedy_ref``)."""
    from repro.serving import Request, ServingEngine
    cfg = reduced(get_config("tinyllama-42m"), dtype="float32")
    params = model.init_params(cfg, PLAN)
    rng = np.random.RandomState(0)
    SB, NSLOT = 64, 4
    # few distinct lengths so the reference's per-length prefill
    # recompiles stay bounded
    reqs = [(rid, rng.randint(2, cfg.vocab_size,
                              int(rng.choice([4, 9, 17]))).astype(np.int32),
             int(rng.randint(2, 8))) for rid in range(8)]
    ref = greedy_reference(cfg, PLAN, mesh1, params,
                           [Request(rid=r, prompt=p, max_new_tokens=m)
                            for r, p, m in reqs], SB)

    peng = ServingEngine.build_paged(cfg, PLAN, mesh1, NSLOT, SB, params,
                                     page_size=8, prefill_chunk=16,
                                     n_pages=2 * (SB // 8) + 1)
    prs = [Request(rid=r, prompt=p, max_new_tokens=m) for r, p, m in reqs]
    for r in prs:
        peng.submit(r)
    peng.run(max_ticks=2000)
    for r in prs:
        assert r.done
        assert tuple(r.out_tokens) == ref[r.rid], r.rid
    assert peng.allocator.n_free == 2 * (SB // 8)   # every page reclaimed


@pytest.mark.slow
def test_paged_engine_randomized_50_requests(mesh1):
    """50 mixed requests complete through a deliberately tight page pool."""
    from repro.serving import Request, ServingEngine
    cfg = reduced(get_config("tinyllama-42m"), dtype="float32")
    params = model.init_params(cfg, PLAN)
    rng = np.random.RandomState(7)
    SB, NSLOT = 32, 4
    eng = ServingEngine.build_paged(cfg, PLAN, mesh1, NSLOT, SB, params,
                                    page_size=8, prefill_chunk=8,
                                    n_pages=9)     # 8 usable pages: tight
    reqs = []
    for rid in range(50):
        L = int(rng.randint(1, 20))
        m = int(rng.randint(1, min(8, SB - L)))
        req = Request(rid=rid,
                      prompt=rng.randint(2, cfg.vocab_size,
                                         L).astype(np.int32),
                      max_new_tokens=m)
        reqs.append(req)
        eng.submit(req)
    stats = eng.run(max_ticks=20_000)
    assert all(r.done for r in reqs)
    assert stats.prefills == 50
    assert len(stats.ttft_s) == 50
    assert stats.decoded_tokens >= 50
    assert eng.allocator.n_free == 8               # pool fully reclaimed


def test_paged_engine_rejects_oversized_request(mesh1):
    from repro.serving import Request, ServingEngine
    cfg = reduced(get_config("tinyllama-42m"), dtype="float32")
    params = model.init_params(cfg, PLAN)
    eng = ServingEngine.build_paged(cfg, PLAN, mesh1, 2, 32, params,
                                    page_size=8, prefill_chunk=8, n_pages=4)
    rng = np.random.RandomState(0)
    # fits the sequence budget but can never fit the 3-usable-page pool:
    # rejected at submit, before any in-flight request can be disrupted
    req = Request(rid=0, prompt=rng.randint(2, cfg.vocab_size,
                                            20).astype(np.int32),
                  max_new_tokens=10)
    with pytest.raises(RuntimeError, match="pages"):
        eng.submit(req)


def test_paged_cache_arch_support():
    """SSM/hybrid (slab pools) and enc-dec (cross pools) are paged now;
    only archs whose prefill needs non-token inputs the chunk step cannot
    carry (vision embeds) are rejected — with a precise reason."""
    from repro.core.kvcache import paged_cache_supported, paged_cache_template
    from repro.core.partition import model_layout
    for name in ("mamba2-370m", "hymba-1.5b", "seamless-m4t-large-v2"):
        cfg = reduced(get_config(name))
        ok, why = paged_cache_supported(cfg)
        assert ok, (name, why)
    cfg = reduced(get_config("mamba2-370m"))
    tmpl = paged_cache_template(cfg, PLAN, model_layout(cfg, PLAN), 8, 4,
                                n_slabs=3)
    # slab pools only: a pure-SSM arch has no KV page pools at all
    kinds = {k for pat in tmpl for d in pat for k in d}
    assert kinds == {"ssm"}
    cfg = reduced(get_config("pixtral-12b"))
    ok, why = paged_cache_supported(cfg)
    assert not ok and "vision" in why
    with pytest.raises(ValueError, match="vision"):
        paged_cache_template(cfg, PLAN, model_layout(cfg, PLAN), 8, 4)


# ---------------------------------------------------------------------------
# quantized slab pools under forced preemption
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_ssm_int8_forced_preemption_identity(mesh1):
    """int8 KV pages + int8 SSM slabs: the preemption stash snapshots the
    quantized slab (raw int8 payload + per-head scales, never a dequant
    round-trip) and the restore writes it back exactly, so greedy outputs
    stay token-identical to the fp oracle with or without preemption."""
    from repro.serving import Request, ServingEngine
    cfg = reduced(get_config("hymba-1.5b"), dtype="float32")
    plan_i8 = ShardingPlan(tp=1, kv_cache_dtype="int8",
                           ssm_cache_dtype="int8")
    params = model.init_params(cfg, PLAN)
    rng = np.random.RandomState(3)
    base = [(rng.randint(2, cfg.vocab_size, L).astype(np.int32), m)
            for L, m in zip([13, 9], [8, 6], strict=True)]

    def run(plan, preempt_at):
        eng = ServingEngine.build_paged(cfg, plan, mesh1, 2, 32, params,
                                        page_size=8, prefill_chunk=8)
        reqs = [Request(rid=i, prompt=p.copy(), max_new_tokens=m)
                for i, (p, m) in enumerate(base)]
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

    ref, _ = run(PLAN, set())                     # fp oracle
    base_i8, _ = run(plan_i8, set())
    assert base_i8 == ref
    for pts in ({1}, {3}, {1, 2, 3}):
        got, eng = run(plan_i8, pts)
        assert got == ref, pts
        assert eng.stats.slab_restores == len(pts)
        for a in eng.allocators:
            assert a.n_free == a.n_pages - a.n_reserved
        assert eng.slab_allocators[0].n_free == eng.n_slabs - 1
