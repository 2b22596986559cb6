"""Quantized page pools: int8 KV/slab/cross payloads with per-page scale
side tensors.  Covers the template gating (scale leaves exist ONLY under an
int8 plan, so fp paths stay bit-identical), the per-row quantizer units,
the int8 dequant-on-read Pallas kernels against the dequant refs, and
engine-level greedy token-identity against the fp oracle."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.core import model
from repro.core.blocks import _row_quant
from repro.core.kvcache import (kv_pool_is_quantized, paged_cache_template,
                                ssm_pool_is_quantized)
from repro.core.partition import ShardingPlan, model_layout

PLAN_FP = ShardingPlan(tp=1, kv_cache_dtype="float32")
PLAN_I8 = ShardingPlan(tp=1, kv_cache_dtype="int8", ssm_cache_dtype="int8")


def _cfg(name="tinyllama-42m"):
    return reduced(get_config(name), dtype="float32")


# ---------------------------------------------------------------------------
# template gating: scale leaves appear only under int8 plans
# ---------------------------------------------------------------------------

def test_plan_predicates():
    assert not kv_pool_is_quantized(PLAN_FP)
    assert not ssm_pool_is_quantized(PLAN_FP)
    assert kv_pool_is_quantized(PLAN_I8)
    assert ssm_pool_is_quantized(PLAN_I8)
    assert not ssm_pool_is_quantized(ShardingPlan(kv_cache_dtype="int8"))


def _template_keys(cfg, plan, n_slabs=0):
    tmpl = paged_cache_template(cfg, plan, model_layout(cfg, plan), 8, 4,
                                n_slabs=n_slabs)
    out = {}
    for pat in tmpl:
        for d in pat:
            for kind, leaves in d.items():
                for k, (shape, dtype, _) in leaves.items():
                    out[(kind, k)] = (shape[1:], dtype)   # strip scan reps
    return out

def test_template_int8_gains_scale_leaves():
    cfg = _cfg()
    fp = _template_keys(cfg, PLAN_FP)
    i8 = _template_keys(cfg, PLAN_I8)
    assert ("kv", "ksp") not in fp and ("kv", "vsp") not in fp
    assert i8[("kv", "kp")][1] == jnp.int8
    # one float32 scale per (replica, page, token slot), pages flat
    for k in ("ksp", "vsp"):
        shape, dtype = i8[("kv", k)]
        assert shape == (1, 8 * 4) and dtype == jnp.float32


def test_template_int8_ssm_and_cross():
    hy = _cfg("hymba-1.5b")
    i8 = _template_keys(hy, PLAN_I8, n_slabs=3)
    assert i8[("ssm", "statep")][1] == jnp.int8
    H = model_layout(hy, PLAN_I8).ssm.hq_loc
    assert i8[("ssm", "sscalep")] == ((1, 3, H), jnp.float32)
    # conv pools are NOT quantized (tiny, precision-critical tails)
    assert i8[("ssm", "conv_xp")][1] != jnp.int8
    fp = _template_keys(hy, PLAN_FP, n_slabs=3)
    assert ("ssm", "sscalep") not in fp
    enc = _cfg("seamless-m4t-large-v2")
    i8e = _template_keys(enc, ShardingPlan(tp=1, kv_cache_dtype="int8"))
    assert i8e[("cross", "ckp")][1] == jnp.int8
    assert i8e[("cross", "cksp")] == ((1, 8 * 4), jnp.float32)


# ---------------------------------------------------------------------------
# per-row quantizer units
# ---------------------------------------------------------------------------

def test_row_quant_roundtrip_and_zero_rows():
    rng = np.random.RandomState(0)
    x = rng.randn(5, 3, 4, 8).astype(np.float32)
    x[2] = 0.0                             # an all-zero row
    q, s = _row_quant(jnp.asarray(x))
    assert q.dtype == jnp.int8 and s.shape == (5, 3)
    back = np.asarray(q, np.float32) * np.asarray(s)[..., None, None]
    # error bounded by half a quantization step per row
    amax = np.abs(x).max(axis=(-2, -1), keepdims=True)
    assert np.all(np.abs(back - x) <= amax / 127.0 * 0.5 + 1e-7)
    assert np.all(np.asarray(q[2]) == 0) and np.all(np.asarray(s[2]) == 0)
    assert np.all(np.asarray(back[2]) == 0)     # zero rows dequant to zero
    # value-determinism: same row value -> same bytes, regardless of batch
    q1, s1 = _row_quant(jnp.asarray(x[1:2]))
    np.testing.assert_array_equal(np.asarray(q[1]), np.asarray(q1[0]))
    np.testing.assert_array_equal(np.asarray(s[1]), np.asarray(s1[0]))


# ---------------------------------------------------------------------------
# int8 read paths vs the dequant refs (pure JAX + Pallas interpret)
# ---------------------------------------------------------------------------

def _quantized_pool(rng, n_pages, H, psz, D):
    pool = rng.randint(-127, 128, (n_pages, H, psz, D)).astype(np.int8)
    scales = (np.abs(rng.randn(n_pages, psz)) * 0.02).astype(np.float32)
    return pool, scales


def _gather_ref(pool_f, bt):
    B, n_max = bt.shape
    n_pages, H, psz, D = pool_f.shape
    g = pool_f[bt.reshape(-1)].reshape(B, n_max, H, psz, D)
    return np.transpose(g, (0, 2, 1, 3, 4)).reshape(B, H, n_max * psz, D)


def test_gather_pages_dequant_matches_ref():
    from repro.core.attention import gather_pages_dequant
    from repro.kernels.ref import ref_dequant_pool
    rng = np.random.RandomState(0)
    kp, ks = _quantized_pool(rng, 9, 2, 4, 16)
    bt = np.stack([rng.permutation(np.arange(1, 9))[:4]
                   for _ in range(2)]).astype(np.int32)
    # the served pools store pages token-major, (n_pages, psz, H, D), and
    # their scales flat, (n_pages * psz,)
    got = gather_pages_dequant(jnp.asarray(kp.transpose(0, 2, 1, 3)),
                               jnp.asarray(ks.reshape(-1)), jnp.asarray(bt),
                               jnp.float32)
    want = _gather_ref(np.asarray(ref_dequant_pool(jnp.asarray(kp),
                                                   jnp.asarray(ks))), bt)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-6, atol=1e-6)


def test_pallas_paged_decode_kernel_int8():
    from repro.kernels.decode_attention import paged_decode_attention
    from repro.kernels.ref import ref_decode_attention, ref_dequant_pool
    rng = np.random.RandomState(1)
    B, H, D, psz, n_max = 3, 2, 32, 8, 4
    n_pages = B * n_max + 1
    kp, ks = _quantized_pool(rng, n_pages, H, psz, D)
    vp, vs = _quantized_pool(rng, n_pages, H, psz, D)
    bt = rng.permutation(np.arange(1, n_pages))[:B * n_max] \
        .reshape(B, n_max).astype(np.int32)
    lens = np.array([5, 30, 17], np.int32)
    q = rng.randn(B, H, D).astype(np.float32)
    out = paged_decode_attention(
        jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp), jnp.asarray(bt),
        jnp.asarray(lens), interpret=True, k_scale=jnp.asarray(ks),
        v_scale=jnp.asarray(vs))
    kf = np.asarray(ref_dequant_pool(jnp.asarray(kp), jnp.asarray(ks)))
    vf = np.asarray(ref_dequant_pool(jnp.asarray(vp), jnp.asarray(vs)))
    expect = ref_decode_attention(jnp.asarray(q),
                                  jnp.asarray(_gather_ref(kf, bt)),
                                  jnp.asarray(_gather_ref(vf, bt)),
                                  jnp.asarray(lens))
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=1e-4, atol=1e-5)


def test_pallas_paged_verify_kernel_int8():
    from repro.kernels.decode_attention import paged_verify_attention
    from repro.kernels.ref import ref_dequant_pool, ref_verify_attention
    rng = np.random.RandomState(2)
    B, H, nq, D, psz, n_max = 2, 2, 5, 32, 8, 4
    n_pages = B * n_max + 1
    kp, ks = _quantized_pool(rng, n_pages, H, psz, D)
    vp, vs = _quantized_pool(rng, n_pages, H, psz, D)
    bt = rng.permutation(np.arange(1, n_pages))[:B * n_max] \
        .reshape(B, n_max).astype(np.int32)
    lens = np.array([9, 22], np.int32)
    q = rng.randn(B, H, nq, D).astype(np.float32)
    out = paged_verify_attention(
        jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp), jnp.asarray(bt),
        jnp.asarray(lens), interpret=True, k_scale=jnp.asarray(ks),
        v_scale=jnp.asarray(vs))
    kf = np.asarray(ref_dequant_pool(jnp.asarray(kp), jnp.asarray(ks)))
    vf = np.asarray(ref_dequant_pool(jnp.asarray(vp), jnp.asarray(vs)))
    expect = ref_verify_attention(jnp.asarray(q),
                                  jnp.asarray(_gather_ref(kf, bt)),
                                  jnp.asarray(_gather_ref(vf, bt)),
                                  jnp.asarray(lens))
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=1e-4, atol=1e-5)


def test_pallas_ssd_scan_int8_state0():
    from repro.kernels.ref import ref_dequant_state, ref_ssd_scan
    from repro.kernels.ssd_scan import ssd_scan
    rng = np.random.RandomState(3)
    S, H, P, N = 64, 2, 8, 16
    x = rng.randn(S, H, P).astype(np.float32)
    dt = (np.abs(rng.randn(S, H)) * 0.1).astype(np.float32)
    Bm = rng.randn(S, N).astype(np.float32)
    Cm = rng.randn(S, N).astype(np.float32)
    A = -np.abs(rng.randn(H)).astype(np.float32)
    s0 = rng.randint(-127, 128, (H, P, N)).astype(np.int8)
    s0s = (np.abs(rng.randn(H)) * 0.02).astype(np.float32)
    y = ssd_scan(jnp.asarray(x), jnp.asarray(dt), jnp.asarray(Bm),
                 jnp.asarray(Cm), jnp.asarray(A), chunk=16, interpret=True,
                 state0=jnp.asarray(s0), state0_scale=jnp.asarray(s0s))
    want, _ = ref_ssd_scan(jnp.asarray(x), jnp.asarray(dt), jnp.asarray(Bm),
                           jnp.asarray(Cm), jnp.asarray(A),
                           state0=ref_dequant_state(jnp.asarray(s0),
                                                    jnp.asarray(s0s)))
    np.testing.assert_allclose(np.asarray(y), np.asarray(want),
                               rtol=1e-4, atol=1e-5)
    # state0=None stays byte-compatible with the original entry point
    y0 = ssd_scan(jnp.asarray(x), jnp.asarray(dt), jnp.asarray(Bm),
                  jnp.asarray(Cm), jnp.asarray(A), chunk=16, interpret=True)
    w0, _ = ref_ssd_scan(jnp.asarray(x), jnp.asarray(dt), jnp.asarray(Bm),
                         jnp.asarray(Cm), jnp.asarray(A))
    np.testing.assert_allclose(np.asarray(y0), np.asarray(w0),
                               rtol=1e-4, atol=1e-5)


# ---------------------------------------------------------------------------
# engine level: int8 pools, greedy token-identity vs the fp oracle
# ---------------------------------------------------------------------------

def _run_engine(cfg, plan, params, mesh, prompts, *, max_new=6, frames=None,
                speculative=0):
    from repro.serving import Request, ServingEngine
    eng = ServingEngine.build_paged(cfg, plan, mesh, 2, 64, params,
                                    page_size=8, prefill_chunk=16,
                                    speculative=speculative)
    reqs = [Request(rid=i, prompt=p.copy(), max_new_tokens=max_new,
                    frames=(frames[i % len(frames)] if frames else None))
            for i, p in enumerate(prompts)]
    for r in reqs:
        eng.submit(r)
    eng.run(max_ticks=3000)
    assert all(r.done for r in reqs)
    for rr, a in enumerate(eng.allocators):
        cached = eng.cross_caches[rr].n_cached_pages if eng.cross_caches \
            else 0
        assert a.n_free + cached == a.n_pages - a.n_reserved   # leak-free
    return {r.rid: tuple(r.out_tokens) for r in reqs}


@pytest.mark.slow
def test_int8_engine_greedy_identity_attention(mesh1):
    cfg = _cfg()
    params = model.init_params(cfg, PLAN_FP)
    rng = np.random.RandomState(0)
    prompts = [rng.randint(2, cfg.vocab_size,
                           rng.randint(4, 20)).astype(np.int32)
               for _ in range(4)]
    ref = _run_engine(cfg, PLAN_FP, params, mesh1, prompts)
    got = _run_engine(cfg, ShardingPlan(tp=1, kv_cache_dtype="int8"),
                      params, mesh1, prompts)
    assert got == ref


@pytest.mark.slow
def test_int8_engine_greedy_identity_encdec(mesh1):
    cfg = _cfg("seamless-m4t-large-v2")
    params = model.init_params(cfg, PLAN_FP)
    rng = np.random.RandomState(1)
    frames = [rng.randn(cfg.enc_seq_len, cfg.d_model).astype(np.float32)
              for _ in range(2)]
    prompts = [rng.randint(2, cfg.vocab_size,
                           rng.randint(4, 16)).astype(np.int32)
               for _ in range(3)]
    ref = _run_engine(cfg, PLAN_FP, params, mesh1, prompts, frames=frames)
    got = _run_engine(cfg, ShardingPlan(tp=1, kv_cache_dtype="int8"),
                      params, mesh1, prompts, frames=frames)
    assert got == ref
