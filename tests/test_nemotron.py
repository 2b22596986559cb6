"""Nemotron-H (interleaved Mamba-2, expert and attention layers) on the CPU:
the layer pattern and its scanned groups, the expert layer that holds a
share of the experts and drops no pick, the expert-pick counts the paged
steps return, and the serving entry points.  The paged engine's logits
against the plain reference are checked by the benchmark's cross-checks
(``bench/tests/test_bench_reference.py`` over
``bench/tests/data/nemotron-tiny.json``)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.configs.base import FFN_MOE, MIX_ATTN, MIX_NONE, MIX_SSM
from repro.core import model
from repro.core.moe import moe_ffn
from repro.core.partition import ShardingPlan

PLAN = ShardingPlan(tp=1)


def _groups(name):
    return [(g.n_reps, len(g.pattern)) for g in get_config(name).layer_groups()]


def test_layer_pattern_gives_each_layer_its_kind():
    cfg = get_config("nemotron3-nano-ep8")
    specs = cfg.layer_specs()
    assert cfg.layer_pattern == "MEMEM*EMEMEM*EMEMEM*E"
    kinds = {"M": (MIX_SSM, "none"), "E": (MIX_NONE, FFN_MOE),
             "*": (MIX_ATTN, "none")}
    assert [(s.mixer, s.ffn) for s in specs] == \
        [kinds[c] for c in cfg.layer_pattern]
    assert [s.cache_kinds() for s in specs[:7]] == \
        [["ssm"], [], ["ssm"], [], ["ssm"], ["kv"], []]
    # an expert layer holds its FFN norm and experts, no mixer or ln1
    t = model.layer_template(cfg, specs[1], cfg.n_layers)
    assert set(t) == {"ln2", "ffn"}
    assert t["ffn"]["experts"]["w_up"].full == (16, 2688, 1856)
    assert t["ffn"]["router"]["w"].full == (2688, 128)
    assert t["ffn"]["shared"]["w_up"].full == (2688, 3712)


def test_published_model_counts_31_6_billion_parameters():
    n = model.param_count(get_config("nemotron3-nano-30b-a3b"))
    assert abs(n / 31.6e9 - 1) < 0.005, n


@pytest.mark.parametrize("name,groups", [
    ("nemotron3-nano-ep8", [(3, 7)]),
    ("nemotron3-nano-30b-a3b", [(5, 7), (1, 8), (4, 2), (1, 1)]),
    # unchanged by period 7
    ("qwen3-0.6b", [(28, 1)]),
    ("mamba2-370m", [(48, 1)]),
    ("gemma3-12b", [(8, 6)]),
    ("gemma3-27b", [(10, 6), (2, 1)]),
    ("hymba-1.5b", [(1, 8), (8, 1), (1, 8), (1, 8)]),
])
def test_factor_layer_groups_finds_period_seven(name, groups):
    assert _groups(name) == groups


def _moe_setup(seed=0, T=24, name="nemotron3-nano-30b-a3b"):
    """A reduced expert layer (8 experts, top 2; relu^2 and a sigmoid router
    for Nemotron) in float32, so that only the order of sums can differ."""
    cfg = reduced(get_config(name))
    spec = [s for s in cfg.layer_specs() if s.ffn == FFN_MOE][0]
    tmpl = model.layer_template(cfg, spec, cfg.n_layers)["ffn"]
    key = jax.random.PRNGKey(seed)

    def draw(node, k):
        if isinstance(node, dict):
            return {n: draw(v, jax.random.fold_in(k, i))
                    for i, (n, v) in enumerate(sorted(node.items()))}
        return jax.random.normal(k, node.full, jnp.float32) * \
            (0.5 if len(node.full) == 1 else node.full[-2] ** -0.5)

    p = draw(tmpl, key)
    x = jax.random.normal(jax.random.fold_in(key, 99), (1, T, cfg.d_model))
    return cfg, p, x


def _per_token(cfg, p, x):
    """The uncut layer written token by token: each picked expert's relu^2
    output weighted by its renormalised, scaled sigmoid score."""
    out = []
    for xt in np.asarray(x[0], np.float64):
        s = 1 / (1 + np.exp(-(xt @ np.asarray(p["router"]["w"]))))
        pick = np.argsort(-(s + np.asarray(p["router"]["bias"])))[:cfg.top_k]
        g = s[pick] / s[pick].sum() * cfg.routed_scale
        y = np.zeros_like(xt)
        for e, ge in zip(pick, g, strict=True):
            h = np.maximum(xt @ np.asarray(p["experts"]["w_up"][e]), 0) ** 2
            y += ge * (h @ np.asarray(p["experts"]["w_down"][e]))
        out.append(y)
    return np.stack(out)


def test_expert_shares_add_up_to_the_uncut_layer():
    """Each chip of an expert-parallel deployment routes over all experts
    and computes its own experts' part; the four shares of two experts
    each, with the shared expert (which every chip computes alike)
    counted once, add up to the uncut layer, and the uncut layer is the
    per-token definition."""
    cfg, p, x = _moe_setup()
    n = cfg.n_experts
    cut = lambda first, held: dataclasses.replace(   # noqa: E731
        cfg, expert_first=first, experts_held=held)
    whole, picks = moe_ffn(x, p, cut(0, n), 0, n)
    parts = []
    for first in range(0, n, 2):
        share = {"router": p["router"], "experts": jax.tree_util.tree_map(
            lambda a, first=first: a[first:first + 2], p["experts"])}
        y, pk = moe_ffn(x, share, cut(first, 2), first, 2)
        parts.append((np.asarray(y), np.asarray(pk)))
    shared = np.asarray(jnp.square(jax.nn.relu(x @ p["shared"]["w_up"]))
                        @ p["shared"]["w_down"])
    # float32 throughout: the shares sum the same terms in another order
    np.testing.assert_allclose(sum(y for y, _ in parts) + shared,
                               np.asarray(whole) + shared,
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(whole)[0], _per_token(cfg, p, x),
                               rtol=1e-4, atol=1e-4)
    # every token's picks land on exactly one share each
    assert (sum(pk for _, pk in parts) == cfg.top_k).all()
    assert (np.asarray(picks) == cfg.top_k).all()


@pytest.mark.parametrize("T", [1, 3, 16, 64])
def test_routing_drops_nothing(T):
    """All tokens routed to the same two experts: every token still gets
    both, as it does alone (a capacity-bounded bucket would drop the
    tokens past its capacity), and a token's output does not depend on
    its batch-mates."""
    cfg, p, x = _moe_setup(seed=1, T=T)
    bias = np.zeros(cfg.n_experts, np.float32)
    bias[[3, 5]] = 100.0                       # every token picks 3 and 5
    p["router"]["bias"] = jnp.asarray(bias)
    y, picks = moe_ffn(x, p, cfg, 0, cfg.n_experts)
    alone = np.stack([np.asarray(moe_ffn(x[:, t:t + 1], p, cfg, 0,
                                         cfg.n_experts)[0])[0, 0]
                      for t in range(T)])
    # float32; one token or many only changes the matmul's row count
    np.testing.assert_allclose(np.asarray(y)[0], alone, rtol=1e-5,
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(y)[0], _per_token(cfg, p, x),
                               rtol=1e-4, atol=1e-4)
    assert (np.asarray(picks) == 2).all()


@pytest.mark.parametrize("T", [1, 64])
def test_softmax_routing_drops_nothing(T):
    """The softmax-routed layers (deepseek-moe, mixtral) share the dropless
    layer: with a zero router every token ties on every expert and picks
    experts 0 and 1 at half weight each, and still gets both, as alone."""
    cfg, p, x = _moe_setup(seed=2, T=T, name="deepseek-moe-16b")
    p["router"]["w"] = jnp.zeros_like(p["router"]["w"])
    y, picks = moe_ffn(x, p, cfg, 0, cfg.n_experts)
    ex = {k: np.asarray(v, np.float64) for k, v in p["experts"].items()}
    xt = np.asarray(x[0], np.float64)

    def expert(e):                              # gated silu
        g = xt @ ex["w_gate"][e]
        return (g / (1 + np.exp(-g)) * (xt @ ex["w_up"][e])) @ ex["w_down"][e]

    # float32 against float64: the layer's sums, in another order
    np.testing.assert_allclose(np.asarray(y)[0],
                               0.5 * (expert(0) + expert(1)),
                               rtol=1e-4, atol=1e-4)
    assert (np.asarray(picks) == cfg.top_k).all()


def _engine(slots=2, budget=128, chunk=32):
    from repro.launch.mesh import host_mesh
    from repro.serving import ServingEngine
    cfg = reduced(get_config("nemotron3-nano-ep8"))
    params = model.init_params(cfg, PLAN, seed=3)
    return ServingEngine.build_paged(
        cfg, PLAN, host_mesh(tp=1, dp=1), slots, budget, params,
        page_size=16, prefill_chunk=chunk, eos_id=-1)


def _serve(eng, prompts, max_new=5):
    from repro.serving import Request
    rows = {}
    sample = eng._sample_row

    def keep(logits, b, req):
        rows.setdefault(req.rid, []).append(np.asarray(logits[b]).copy())
        return sample(logits, b, req)

    eng._sample_row = keep
    reqs = [Request(rid=i, prompt=np.asarray(pr, np.int32),
                    max_new_tokens=max_new) for i, pr in enumerate(prompts)]
    for r in reqs:
        eng.submit(r)
    eng.run()
    eng._sample_row = sample
    # the greedy engine picks its tokens on the device and hands the row
    # sampler rows it fetches only when read: every emitted token's row
    # was read, and the token is its argmax
    for r in reqs:
        assert len(rows[r.rid]) == len(r.out_tokens) == max_new
        assert r.out_tokens == [int(np.argmax(x[:eng.cfg.vocab_size]))
                                for x in rows[r.rid]]
    return reqs, rows


def test_paged_rows_do_not_depend_on_batch_mates():
    """A request's logits are the same served alone or beside another
    request (prefill chunks and full-batch decode with other rows live)."""
    rng = np.random.default_rng(5)
    a, b = rng.integers(2, 500, 45), rng.integers(2, 500, 70)
    _, alone = _serve(_engine(), [a])
    _, both = _serve(_engine(), [a, b])
    for x, y in zip(alone[0], both[0], strict=True):
        np.testing.assert_array_equal(x, y)


def test_steps_count_expert_picks_beside_each_token():
    cfg = reduced(get_config("nemotron3-nano-ep8"))
    assert model.counts_expert_picks(cfg)
    rng = np.random.default_rng(6)
    prompts = [rng.integers(2, 500, n) for n in (45, 70, 9)]
    reqs, _ = _serve(_engine(), prompts)
    n_e = cfg.layer_pattern.count("E")
    for r in reqs:
        assert len(r.expert_picks) == len(r.out_tokens) == 5
        # the first token carries its whole prompt's picks
        assert 0 <= r.expert_picks[0] <= n_e * cfg.top_k * len(r.prompt)
        assert all(0 <= n <= n_e * cfg.top_k for n in r.expert_picks[1:])
    assert sum(sum(r.expert_picks) for r in reqs) > 0


def test_models_without_experts_return_nothing_new():
    from repro.launch.mesh import host_mesh
    from repro.serving import ServingEngine
    cfg = reduced(get_config("qwen3-0.6b"))
    assert not model.counts_expert_picks(cfg)
    eng = ServingEngine.build_paged(
        cfg, PLAN, host_mesh(tp=1, dp=1), 2, 64,
        model.init_params(cfg, PLAN), page_size=16, prefill_chunk=16)
    reqs, _ = _serve(eng, [np.arange(2, 20)], max_new=3)
    assert reqs[0].expert_picks == [] and len(reqs[0].out_tokens) == 3
    # every model with expert layers counts them, through the one layer
    assert model.counts_expert_picks(reduced(get_config("deepseek-moe-16b")))


def test_serve_entry_point_builds_the_engine_and_refuses_prefix_cache():
    from repro.launch import serve
    base = ["--arch", "nemotron3-nano-ep8", "--smoke",
            "--slots", "2", "--seq-budget", "64", "--page-size", "16",
            "--prefill-chunk", "16"]
    eng = serve.build_engine(serve.parse_args(base))
    assert eng.cfg.layer_pattern == "MEMEM*E" and eng.has_ssm
    assert eng.counts_picks
    with pytest.raises(ValueError, match="SSM layers hold recurrent state"):
        serve.build_engine(serve.parse_args(base + ["--prefix-cache"]))
