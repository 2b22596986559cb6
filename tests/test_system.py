"""End-to-end behaviour tests: serving engine, analytics, attention module,
collectives ledger, shape-support matrix."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.configs.base import SHAPES, ShapeConfig, shape_supported
from repro.core import analytics, collectives as cc, model, steps
from repro.core.partition import ShardingPlan


# ---------------------------------------------------------------------------
# attention module vs kernel oracle
# ---------------------------------------------------------------------------

def test_core_flash_matches_ref():
    from repro.core.attention import flash_attention
    from repro.kernels import ref
    rng = np.random.RandomState(0)
    B, G, R, S, D = 2, 2, 3, 96, 32
    q = jnp.asarray(rng.randn(B, G, R, S, D), jnp.float32)
    k = jnp.asarray(rng.randn(B, G, S, D), jnp.float32)
    v = jnp.asarray(rng.randn(B, G, S, D), jnp.float32)
    out = flash_attention(q, k, v, causal=True, q_block=32, kv_block=32)
    for b in range(B):
        for g in range(G):
            for r in range(R):
                expect = ref.ref_flash_attention(q[b, g, r][None],
                                                 k[b, g][None], v[b, g][None])
                np.testing.assert_allclose(np.asarray(out[b, g, r]),
                                           np.asarray(expect[0]),
                                           rtol=1e-4, atol=1e-4)


def test_core_flash_window_matches_ref():
    from repro.core.attention import flash_attention
    from repro.kernels import ref
    rng = np.random.RandomState(1)
    q = jnp.asarray(rng.randn(1, 1, 1, 256, 16), jnp.float32)
    k = jnp.asarray(rng.randn(1, 1, 256, 16), jnp.float32)
    v = jnp.asarray(rng.randn(1, 1, 256, 16), jnp.float32)
    out = flash_attention(q, k, v, causal=True, window=48, q_block=64,
                          kv_block=32)
    expect = ref.ref_flash_attention(q[0, 0], k[0], v[0], causal=True,
                                     window=48)
    np.testing.assert_allclose(np.asarray(out[0, 0, 0]),
                               np.asarray(expect[0]), rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# serving engine end-to-end
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_serving_engine_end_to_end(mesh1):
    from repro.serving import Request, SamplerConfig, ServingEngine
    cfg = reduced(get_config("tinyllama-42m"))
    plan = ShardingPlan(tp=1)
    params = model.init_params(cfg, plan)
    SB = 64
    eng = ServingEngine.build_paged(cfg, plan, mesh1, 2, SB, params,
                                    sampler=SamplerConfig())
    rng = np.random.RandomState(0)
    for rid in range(4):
        eng.submit(Request(rid=rid,
                           prompt=rng.randint(2, cfg.vocab_size, 8,
                                              ).astype(np.int32),
                           max_new_tokens=6))
    stats = eng.run(max_ticks=200)
    assert stats.prefills == 4
    assert stats.decoded_tokens >= 4 * 1
    assert len(stats.ttft_s) == 4


@pytest.mark.slow
def test_greedy_decode_deterministic(mesh1):
    """Same prompt -> same continuation (greedy), incl. after cache reuse."""
    from repro.serving import Request, SamplerConfig, ServingEngine
    cfg = reduced(get_config("qwen3-0.6b"))
    plan = ShardingPlan(tp=1)
    params = model.init_params(cfg, plan)
    SB = 32
    outs = []
    for _ in range(2):
        eng = ServingEngine.build_paged(cfg, plan, mesh1, 1, SB, params,
                                        sampler=SamplerConfig())
        req = Request(rid=0, prompt=np.arange(2, 10, dtype=np.int32),
                      max_new_tokens=5)
        eng.submit(req)
        eng.run(max_ticks=50)
        outs.append(tuple(req.out_tokens))
    assert outs[0] == outs[1]


def test_serve_cli_default_builds_paged_engine(monkeypatch, capsys):
    """``launch/serve.py`` with no engine flags serves through the paged
    engine: every request completes and every page returns to the pool."""
    from repro.launch import compile_cache, serve
    monkeypatch.setattr(compile_cache, "enable_compile_cache", lambda: "")
    built = []
    build = serve.build_engine
    monkeypatch.setattr(serve, "build_engine",
                        lambda args: built.append(build(args)) or built[-1])
    assert serve.main(["--arch", "tinyllama-42m", "--smoke",
                       "--requests", "3"]) == 0
    (eng,) = built
    assert eng.allocators
    for a in eng.allocators:
        assert a.n_free == a.n_pages - a.n_reserved
    out = capsys.readouterr().out
    assert "requests=3 " in out and "prefills=3 " in out


# ---------------------------------------------------------------------------
# analytics / ledger invariants
# ---------------------------------------------------------------------------

def test_analytic_flops_match_cost_analysis_unrolled(mesh1):
    """Analytic model vs XLA cost_analysis on a small UNROLLED module."""
    cfg = reduced(get_config("qwen3-0.6b"), n_layers=2)
    plan = ShardingPlan(tp=1)
    B, S = 2, 128
    from repro.core.partition import model_layout
    lay = model_layout(cfg, plan)
    params = model.abstract_params(cfg, plan)

    def fwd(p, tokens):
        positions = jnp.broadcast_to(jnp.arange(S), (B, S))
        x = model.embed_tokens(p, tokens, cfg, plan, lay)
        x, _ = model._run_stack(x, p["stacks"], cfg.layer_groups(), cfg,
                                plan, lay, "train", positions)
        from repro.core.layers import apply_norm
        x = apply_norm(x, p["final_norm"], cfg)
        return model.final_logits(p, x, cfg, lay)

    with mesh1:
        compiled = jax.jit(fwd).lower(
            params, jax.ShapeDtypeStruct((B, S), jnp.int32)).compile()
    ca = compiled.cost_analysis()
    if isinstance(ca, (list, tuple)):   # JAX < 0.5: one dict per device
        ca = ca[0]
    hlo_flops = ca["flops"]

    cc.set_axis_sizes({"data": 1, "model": 1})
    shape = ShapeConfig("t", "prefill", S, B)
    cost = analytics.step_cost(cfg, plan, shape, {"data": 1, "model": 1})
    analytic = cost.total_flops
    ratio = analytic / hlo_flops
    assert 0.5 < ratio < 2.2, (analytic, hlo_flops)


def test_two_sync_contract_all_dense_archs(mesh1):
    """The ledger audits exactly 2 block syncs per dense layer."""
    cfg = reduced(get_config("mistral-large-123b"))
    plan = ShardingPlan(tp=1)
    shape = ShapeConfig("t", "train", 32, 2)
    cc.LEDGER.start()
    ts, _ = steps.make_train_step(cfg, plan, mesh1, shape=shape)
    batch = {"tokens": jax.ShapeDtypeStruct((2, 32), jnp.int32),
             "labels": jax.ShapeDtypeStruct((2, 32), jnp.int32)}
    jax.eval_shape(ts, steps.abstract_train_state(cfg, plan), batch)
    cc.LEDGER.stop()
    assert cc.LEDGER.sync_count("block/") == 2 * cfg.n_layers


def test_shape_support_matrix():
    from repro.configs import ASSIGNED
    cells = [(a, s) for a in ASSIGNED for s in SHAPES]
    assert len(cells) == 44
    skipped = [(a, s) for a, s in cells
               if not shape_supported(get_config(a), SHAPES[s])[0]]
    assert len(skipped) == 6
    assert all(s == "long_500k" for _, s in skipped)


def test_param_counts_sane():
    expect = {
        "mamba2-370m": (330e6, 460e6),
        "qwen3-0.6b": (500e6, 800e6),
        "gemma3-12b": (10e9, 14.5e9),
        "gemma3-27b": (24e9, 30e9),
        "mistral-large-123b": (115e9, 130e9),
        "deepseek-moe-16b": (15e9, 19e9),
        "mixtral-8x22b": (130e9, 150e9),
        "pixtral-12b": (11e9, 14e9),
        "tinyllama-42m": (30e6, 60e6),
    }
    for name, (lo, hi) in expect.items():
        n = model.param_count(get_config(name))
        assert lo < n < hi, (name, n)


def test_sim_reproduces_paper_claims():
    """Paper Fig.4/5/6 headline numbers within documented tolerance."""
    from benchmarks.fig4_speedup import derived as d4
    from benchmarks.fig5_energy import derived as d5
    from benchmarks.fig6_scalability import derived as d6

    def ratio(s):
        a, b = s.split("/")
        return float(a) / float(b)

    r4 = d4()
    assert 0.8 < ratio(r4["ar_speedup8_sim_vs_paper"]) < 1.25
    assert 0.8 < ratio(r4["prompt_speedup8_sim_vs_paper"]) < 1.25
    assert r4["ar_memory_dominated_1chip"]
    r5 = d5()
    assert 0.7 < ratio(r5["ar8_ms_sim_vs_paper"]) < 1.3
    assert 0.6 < ratio(r5["ar8_mj_sim_vs_paper"]) < 1.4
    assert r5["resident_at_32chips"] and r5["energy_drops_when_resident"]
    r6 = d6()
    assert 0.85 < ratio(r6["ar_speedup64_sim_vs_paper"]) < 1.2
    assert r6["prompt_diminishing_returns_past_16"]


# ---------------------------------------------------------------------------
# beyond-paper optimization paths (§Perf hillclimbs) — correctness
# ---------------------------------------------------------------------------

def test_flash_attention_split_exact():
    """Recursive causal splitting (hillclimb 2) is exact vs the oracle."""
    from repro.core.attention import flash_attention_split
    from repro.kernels import ref
    rng = np.random.RandomState(3)
    B, G, R, S, D = 1, 2, 1, 512, 32
    q = jnp.asarray(rng.randn(B, G, R, S, D), jnp.float32)
    k = jnp.asarray(rng.randn(B, G, S, D), jnp.float32)
    v = jnp.asarray(rng.randn(B, G, S, D), jnp.float32)
    out = flash_attention_split(q, k, v, q_block=64, kv_block=64, depth=3)
    for g in range(G):
        expect = ref.ref_flash_attention(q[0, g], k[0, g][None].repeat(R, 0),
                                         v[0, g][None].repeat(R, 0))
        np.testing.assert_allclose(np.asarray(out[0, g]), np.asarray(expect),
                                   rtol=1e-4, atol=1e-4)


def test_int8_kv_cache_decode_close(mesh1):
    """int8 KV (hillclimb 1) stays close to bf16-KV decode logits."""
    cfg = reduced(get_config("qwen3-0.6b"), dtype="float32")
    rng = np.random.RandomState(0)
    B, S = 2, 32
    params = model.init_params(cfg, ShardingPlan(tp=1))
    tokens = jnp.asarray(rng.randint(0, cfg.vocab_size, (B, S)), jnp.int32)
    outs = {}
    for dt in ("float32", "int8"):
        plan = ShardingPlan(tp=1, kv_cache_dtype=dt)
        dec, _, _ = steps.make_decode_step(cfg, plan, mesh1,
                                           ShapeConfig("d", "decode", S, B))
        dec = jax.jit(dec)
        cache = steps.zero_cache_for(cfg, plan, mesh1, B, S)
        with mesh1:
            for t in range(8):
                lg, cache = dec(params, cache, tokens[:, t:t + 1],
                                jnp.full((B,), t, jnp.int32))
        outs[dt] = np.asarray(lg, np.float64)
    err = np.abs(outs["float32"] - outs["int8"]).max()
    assert err < 0.3, err          # quantization-level, not divergence


def test_context_parallel_ssm_subprocess():
    """CP (hillclimb 3): mamba2 loss identical to single-device reference.
    (Validated standalone with 8 host devices; here we assert the CP code
    path at cp=1 degrades to the reference exactly.)"""
    cfg = reduced(get_config("mamba2-370m"), dtype="float32")
    rng = np.random.RandomState(0)
    B, S = 2, 64
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((1, 1), ("data", "model"), devices=jax.devices()[:1])
    tokens = jnp.asarray(rng.randint(0, cfg.vocab_size, (B, S)), jnp.int32)
    batch = {"tokens": tokens, "labels": tokens}
    losses = []
    for plan in (ShardingPlan(tp=1), ShardingPlan(tp=1, cp_axes=("model",))):
        state = steps.init_train_state(cfg, plan)
        ts, _ = steps.make_train_step(cfg, plan, mesh,
                                      shape=ShapeConfig("t", "train", S, B))
        with mesh:
            _, stats = jax.jit(ts)(state, batch)
        losses.append(float(stats["loss"]))
    assert abs(losses[0] - losses[1]) < 1e-6
