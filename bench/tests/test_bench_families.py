"""A model family is found by name, from its own modules alone: the
harness holds no table or class of any family, runs a family registered
under a new name end to end, lays canonical layer tensors out by rank on a
program whose layers differ, checks sizes that are not numbers, and serves
at the tp and dp that the configuration states."""
import json
import os
import re
import subprocess
import sys
import types
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
for _p in (str(ROOT), str(ROOT / "src"), str(Path(__file__).parent)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import cellfiles  # noqa: E402

GENERIC = ("program", "arith", "run", "check", "calibrate", "loop", "trace",
           "traffic", "weights", "phases", "families/__init__")


def _register(monkeypatch, name: str, adapter, reference=None):
    monkeypatch.setitem(sys.modules, f"bench.families.{name}", adapter)
    if reference is not None:
        monkeypatch.setitem(sys.modules, f"bench.reference.{name}", reference)


def test_generic_harness_names_no_family():
    fams = sorted(p.stem for p in (ROOT / "bench" / "families").glob("*.py")
                  if p.stem != "__init__")
    assert {"qwen3", "mamba2"} <= set(fams)
    pat = re.compile("|".join(fams), re.IGNORECASE)
    for f in GENERIC:
        text = (ROOT / "bench" / f"{f}.py").read_text()
        assert not pat.search(text), (f, pat.search(text).group())


def test_a_family_registered_under_a_new_name_runs_end_to_end(
        tmp_path, capsys, monkeypatch):
    from bench import run
    from bench.families import qwen3 as adapter
    from bench.reference import qwen3 as reference
    _register(monkeypatch, "qwen3alias", adapter, reference)
    config = cellfiles.tiny_config()
    config["reference"] = "qwen3alias"
    bench_file = cellfiles.write(tmp_path, config)
    assert run.main(cellfiles.argv(bench_file), require_chip=False) == 0
    out = cellfiles.last_json(capsys.readouterr().out)
    assert out["correct"] is True
    assert out["check"]["tokens_compared"]["value"] > 0


def test_cost_model_is_the_family_adapters(monkeypatch):
    from bench import arith
    from bench.families import qwen3 as adapter
    _register(monkeypatch, "qwen3alias", adapter)
    c = json.loads((ROOT / "bench/configs/qwen3-0.6b.json").read_text())
    want = arith.model_costs(c)
    got = arith.model_costs(dict(c, reference="qwen3alias"))
    assert type(got) is type(want)
    assert got.decode_token(1000) == want.decode_token(1000)
    assert got.prefill(300, 128) == want.prefill(300, 128)


def test_a_missing_family_names_its_file():
    from bench import arith, program
    c = dict(cellfiles.tiny_config(), reference="nosuchfamily")
    with pytest.raises(SystemExit, match=r"bench/families/nosuchfamily\.py"):
        arith.model_costs(c)
    with pytest.raises(SystemExit, match=r"bench/families/nosuchfamily\.py"):
        program.model_config(c)


def test_sizes_may_be_functions_compared_by_equality(monkeypatch):
    from bench import program
    from bench.families import qwen3 as base
    adapter = types.ModuleType("pattern")
    adapter.__dict__.update({k: getattr(base, k) for k in
                             ("LAYOUT", "OFFSET_NORMS", "costs")})
    adapter.SIZES = dict(base.SIZES, layer_types=lambda cfg: [
        "full_attention"] * cfg.n_layers)
    _register(monkeypatch, "qwen3pattern", adapter)
    c = dict(cellfiles.tiny_config(), reference="qwen3pattern",
             layer_types=["full_attention", "full_attention"])
    assert program.model_config(c).n_layers == 2
    c["layer_types"] = ["full_attention", "sliding_attention"]
    with pytest.raises(SystemExit, match="layer_types"):
        program.model_config(c)
    # a number stated otherwise is refused as before
    with pytest.raises(SystemExit, match="d_ff"):
        program.model_config(dict(c, intermediate_size=512))


def test_weights_take_an_unknown_init_from_the_family(monkeypatch):
    import jax.numpy as jnp

    from bench import weights
    adapter = types.ModuleType("inits")
    adapter.INITS = {"twos": lambda key, shape, scale: jnp.full(
        shape, 2.0 * scale, jnp.float32)}
    _register(monkeypatch, "initfamily", adapter)
    spec = {"a": ((3,), "bfloat16", "twos", 1.5),
            "b": ((2, 2), "float32", "normal", 1.0)}
    w = weights.canonical(spec, 5, "initfamily")
    assert np.asarray(w["a"], np.float32).tolist() == [3.0, 3.0, 3.0]
    # a built-in draw is the same whatever the family
    assert np.array_equal(w["b"], weights.canonical(
        {"b": spec["b"]}, 5, "qwen3")["b"])
    with pytest.raises(ValueError, match="twos"):
        weights.canonical(spec, 5, "qwen3")


# A small adapter for deepseek-moe-16b's program: its first layer has a
# dense FFN and the others a routed one, so the FFN's weights live in some
# layers only.
MOE_LAYOUT = {
    "top": {"embed": ("embed", "table"), "final_norm": ("final_norm", "scale"),
            "lm_head": ("lm_head", "w")},
    "layer": {"ln1": ("ln1", "scale"), "q": ("attn", "wq"),
              "k": ("attn", "wk"), "v": ("attn", "wv"), "o": ("attn", "wo"),
              "ln2": ("ln2", "scale"), "dense_up": ("ffn", "w_up"),
              "dense_down": ("ffn", "w_down"),
              "dense_gate": ("ffn", "w_gate"),
              "router": ("ffn", "router", "w"),
              "expert_up": ("ffn", "experts", "w_up"),
              "expert_down": ("ffn", "experts", "w_down"),
              "expert_gate": ("ffn", "experts", "w_gate"),
              "shared_up": ("ffn", "shared", "w_up"),
              "shared_down": ("ffn", "shared", "w_down"),
              "shared_gate": ("ffn", "shared", "w_gate")},
}


def _moe_setup(monkeypatch, n_layers):
    from bench import program
    from repro.configs import get_config, reduced
    from repro.core import model
    from repro.core.partition import ShardingPlan
    from repro.launch.mesh import host_mesh
    adapter = types.ModuleType("moetest")
    adapter.LAYOUT, adapter.OFFSET_NORMS = MOE_LAYOUT, set()
    _register(monkeypatch, "moetest", adapter)
    cfg = reduced(get_config("deepseek-moe-16b"), n_layers=n_layers)
    plan = ShardingPlan(tp=1)
    tmpl = model.model_template(cfg)
    abstract = model.abstract_params(cfg, plan)
    holders = program.layer_holders(cfg, tmpl)
    spec = {}
    top = {k: v for k, v in tmpl.items() if k != "stacks"}
    for name, path in MOE_LAYOUT["top"].items():
        ps = program._paths(top)[path]
        spec[name] = (ps.full, str(program._paths(abstract)[path].dtype),
                      "normal", 1.0)
    for g, gt in enumerate(tmpl["stacks"]):
        for pi, lt in enumerate(gt):
            want = program._paths(abstract["stacks"][g][pi])
            for path, ps in program._paths(lt).items():
                name = {v: k for k, v in MOE_LAYOUT["layer"].items()}[path]
                spec[name] = ((len(holders[path]),) + tuple(ps.full),
                              str(want[path].dtype), "normal", 1.0)
    return cfg, plan, host_mesh(tp=1, dp=1), tmpl, holders, spec


@pytest.mark.parametrize("n_layers", [4, 9])
def test_layer_tensors_are_laid_out_by_rank(monkeypatch, n_layers):
    """Row r of a canonical layer tensor goes to the r-th layer that holds
    its path: the dense FFN's one row to layer 0, the routed FFN's rows to
    layers 1.. (4 layers: one group of four different layers; 9 layers: a
    dense group and a group of 8 repeated MoE layers)."""
    from bench import program, weights
    cfg, plan, mesh, tmpl, holders, spec = _moe_setup(monkeypatch, n_layers)
    assert holders[("ffn", "w_up")] == [0]
    assert holders[("ffn", "router", "w")] == list(range(1, n_layers))
    assert holders[("attn", "wq")] == list(range(n_layers))
    params = program.program_params(cfg, plan, mesh, "moetest", spec, 9)
    canon = weights.canonical(spec, 9, "moetest")
    by_path = {v: k for k, v in MOE_LAYOUT["layer"].items()}
    seen = set()
    offset = 0
    for g, (group, gt) in enumerate(zip(cfg.layer_groups(), tmpl["stacks"],
                                        strict=True)):
        plen = len(group.pattern)
        for pi, lt in enumerate(gt):
            got = program._paths(params["stacks"][g][pi])
            for path in program._paths(lt):
                name = by_path[path]
                for j in range(group.n_reps):
                    r = holders[path].index(offset + pi + plen * j)
                    row = np.asarray(canon[name][r])
                    assert np.array_equal(
                        np.asarray(got[path][j]).reshape(row.shape), row), \
                        (name, offset + pi + plen * j, r)
                    seen.add((name, r))
        offset += group.n_layers
    assert seen == {(n, r) for n, (shape, *_) in spec.items()
                    if n in MOE_LAYOUT["layer"] for r in range(shape[0])}


def test_a_layer_tensor_of_the_wrong_length_is_refused(monkeypatch):
    from bench import program
    cfg, plan, mesh, _, _, spec = _moe_setup(monkeypatch, 4)
    shape, *rest = spec["router"]
    spec["router"] = ((4,) + shape[1:], *rest)
    with pytest.raises(SystemExit, match="router has 4 rows.*3 layers"):
        program.program_params(cfg, plan, mesh, "moetest", spec, 9)


def test_refuses_a_cell_whose_chips_differ_from_tp_times_dp(tmp_path):
    from bench import run
    config = cellfiles.tiny_config()
    config["serving"]["tp"] = 2
    bench_file = cellfiles.write(tmp_path, config, chips=1)
    with pytest.raises(SystemExit, match="1 chips.*tp=2 x dp=1"):
        run.main(cellfiles.argv(bench_file), require_chip=False)


def test_scratch_cell_at_tp2_on_two_host_devices(tmp_path):
    """The harness builds the engine at the configuration's tp: the tiny
    cell at tp=2 (4 query and 2 KV heads, one KV head a shard) on two
    forced host devices serves correct tokens."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, str(Path(cellfiles.__file__)),
                        str(tmp_path)], capture_output=True, text=True,
                       env=env, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    out = cellfiles.last_json(r.stdout)
    assert out["correct"] is True, r.stderr[-2000:]
    assert out["device"]["count"] == 2
    assert "mesh {'data': 1, 'model': 2} tp 2" in r.stderr
