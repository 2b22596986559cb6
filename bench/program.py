"""The system under test as the benchmark drives it: the paged ServingEngine.

``build`` makes the engine the way ``repro.launch.serve.build_engine``
does for a one-replica paged deployment (plan, mesh, sampler,
``ServingEngine.build_paged``), except that the weights are the
benchmark's own: drawn by ``bench/weights.py`` from the seed and laid out
for the program by ``program_params`` in the same jitted call, straight
into the shardings the program's steps expect.  Everything else (pools,
scheduler, compiled steps) is the program's.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights

# Per reference family: the program's parameter path for each canonical
# weight.  Top-level names map to a path from the root, layer names to a
# path inside one layer of ``stacks``.
LAYOUT = {
    "qwen3": {
        "top": {"embed": ("embed", "table"),
                "final_norm": ("final_norm", "scale")},
        "layer": {"ln1": ("ln1", "scale"), "q": ("attn", "wq"),
                  "k": ("attn", "wk"), "v": ("attn", "wv"),
                  "o": ("attn", "wo"), "q_norm": ("attn", "q_norm"),
                  "k_norm": ("attn", "k_norm"), "ln2": ("ln2", "scale"),
                  "gate": ("ffn", "w_gate"), "up": ("ffn", "w_up"),
                  "down": ("ffn", "w_down")},
    },
    "mamba2": {
        "top": {"embed": ("embed", "table"),
                "final_norm": ("final_norm", "scale")},
        "layer": {"norm": ("ln1", "scale"), "in_z": ("ssm", "in_z"),
                  "in_x": ("ssm", "in_x"), "in_B": ("ssm", "in_B"),
                  "in_C": ("ssm", "in_C"), "in_dt": ("ssm", "in_dt"),
                  "conv_x": ("ssm", "conv_x"), "conv_B": ("ssm", "conv_B"),
                  "conv_C": ("ssm", "conv_C"), "dt_bias": ("ssm", "dt_bias"),
                  "A_log": ("ssm", "A_log"), "D": ("ssm", "D"),
                  "gnorm": ("ssm", "norm_scale"), "out": ("ssm", "out")},
    },
}

# The program's RMSNorms multiply by (1 + scale); the canonical weights
# hold the multiplier itself, as published.
OFFSET_NORMS = {"final_norm", "ln1", "ln2", "q_norm", "k_norm", "norm",
                "gnorm"}

# The program's config attribute for each size the configuration file
# states, per family (file key -> attribute); the harness refuses to run
# a program whose model differs from the file.  ``vocab_size`` is the
# embedding's rows: the file's vocabulary padded to its
# ``pad_vocab_size_multiple``, where it has one.
SIZES = {
    "qwen3": {"num_hidden_layers": "n_layers", "hidden_size": "d_model",
              "num_attention_heads": "n_heads",
              "num_key_value_heads": "n_kv_heads", "head_dim": "head_dim_",
              "intermediate_size": "d_ff", "vocab_size": "vocab_size",
              "rope_theta": "rope_theta", "rms_norm_eps": "norm_eps"},
    "mamba2": {"n_layer": "n_layers", "d_model": "d_model",
               "d_state": "ssm_state", "expand": "ssm_expand",
               "headdim": "ssm_head_dim", "d_conv": "ssm_conv",
               "vocab_size": "vocab_size", "norm_epsilon": "norm_eps"},
}
# Attributes the program takes as options: the harness sets them to what
# the file states before the check above, so that the program runs as the
# configuration says.
OPTIONS = ("vocab_size", "norm_eps")


def stated(cfgfile: dict, key: str) -> float:
    """The number the file states for ``key`` (the padded vocabulary for
    ``vocab_size``)."""
    v = cfgfile[key]
    if key == "vocab_size":
        m = int(cfgfile.get("pad_vocab_size_multiple", 1))
        v = -(-int(v) // m) * m
    return v


def model_config(cfgfile: dict):
    """The program's ModelConfig for a configuration file: its options
    set as the file states, and its sizes checked against the file."""
    from repro.configs import get_config, reduced
    cfg = get_config(cfgfile["arch"])
    if cfgfile.get("smoke"):
        cfg = reduced(cfg)
    sizes = SIZES[cfgfile["reference"]]
    cfg = dataclasses.replace(cfg, **{
        attr: type(getattr(cfg, attr))(stated(cfgfile, key))
        for key, attr in sizes.items() if attr in OPTIONS})
    for key, attr in sizes.items():
        if float(getattr(cfg, attr)) != float(stated(cfgfile, key)):
            raise SystemExit(f"bench: the program's {cfg.name}.{attr} = "
                             f"{getattr(cfg, attr)}, the configuration "
                             f"file states {key} = {cfgfile[key]}")
    return cfg


def program_params(cfg, plan, mesh, fam: str, spec: dict, seed32: int):
    """-> the program's parameter tree holding the canonical weights of
    ``spec`` for ``seed32``, made in one jitted call into the shardings of
    ``model.param_pspecs``."""
    from jax.sharding import NamedSharding

    from repro.core import model
    from repro.core.partition import model_layout
    lay = model_layout(cfg, plan)
    tmpl = model.model_template(cfg)
    abstract = model.abstract_params(cfg, plan)
    pspecs = model.param_pspecs(cfg, plan)
    layout = LAYOUT[fam]
    by_path = {v: k for k, v in layout["layer"].items()}
    top = {v: k for k, v in layout["top"].items()}
    used = set()

    def convert(name, full, pspec, want):
        if name in OFFSET_NORMS:
            full = (full.astype(jnp.float32) - 1.0).astype(full.dtype)
        if full.dtype != want.dtype:
            raise SystemExit(f"bench: weight {name} is {full.dtype}, the "
                             f"program serves {want.dtype}")
        # the head mask multiplies by 0/1 (exact); cast back, as
        # model.init_params does
        return model._mask_invalid_heads(
            pspec, model.shard_full(pspec, full, cfg, plan, lay), cfg,
            plan, lay).astype(want.dtype)

    def build(key):
        canon = weights.generate(spec, key)
        out = {}
        for tkey, node in tmpl.items():
            if tkey == "stacks":
                continue
            out[tkey] = {}
            for leaf, ps in node.items():
                name = top[(tkey, leaf)]
                used.add(name)
                full = canon[name].reshape(ps.full)
                out[tkey][leaf] = convert(name, full, ps,
                                          abstract[tkey][leaf])
        stacks, offset = [], 0
        for g, (group, gt) in enumerate(zip(cfg.layer_groups(),
                                            tmpl["stacks"], strict=True)):
            plen = len(group.pattern)
            per_pat = []
            for pi, lt in enumerate(gt):
                idx = offset + pi + plen * np.arange(group.n_reps)
                node = {}
                for sub, leaves in lt.items():
                    node[sub] = {}
                    for leaf, ps in leaves.items():
                        name = by_path[(sub, leaf)]
                        used.add(name)
                        full = canon[name][idx].reshape(
                            (group.n_reps,) + tuple(ps.full))
                        want = abstract["stacks"][g][pi][sub][leaf]
                        node[sub][leaf] = jax.vmap(
                            lambda f, ps=ps, name=name, want=want: convert(
                                name, f, ps, jax.ShapeDtypeStruct(
                                    want.shape[1:], want.dtype)))(full)
                per_pat.append(node)
            stacks.append(per_pat)
            offset += group.n_layers
        out["stacks"] = stacks
        return out

    shardings = jax.tree_util.tree_map(lambda p: NamedSharding(mesh, p),
                                       pspecs)
    params = jax.jit(build, out_shardings=shardings)(
        jax.random.PRNGKey(seed32))
    if used != set(spec):
        raise SystemExit(f"bench: canonical weights the program does not "
                         f"hold: {sorted(set(spec) - used)}")
    return params


# No token ends a request: every request runs to the output length its
# mix scheduled, whatever token random weights put first, so the seed
# changes the tokens and never the work.
NO_EOS = -1


def build(cfgfile: dict, cell: dict, mix: dict, spec: dict, seed32: int,
          engine_seed32: int):
    """-> the paged ServingEngine of this cell, holding the seed's weights."""
    from repro.core.partition import ShardingPlan
    from repro.launch.mesh import host_mesh
    from repro.serving import SamplerConfig, ServingEngine
    cfg = model_config(cfgfile)
    srv = cfgfile["serving"]
    plan = ShardingPlan(tp=1, kv_cache_dtype=srv["kv_dtype"],
                        ssm_cache_dtype=srv.get("ssm_state_dtype", ""))
    mesh = host_mesh(tp=1, dp=1)
    params = program_params(cfg, plan, mesh, cfgfile["reference"], spec,
                            seed32)
    samp = mix.get("sampling", {})
    sampler = SamplerConfig(temperature=float(samp.get("temperature", 0.0)),
                            top_k=int(samp.get("top_k", 0)))
    return ServingEngine.build_paged(
        cfg, plan, mesh, cell["slots"], cell["seq_budget"], params,
        page_size=srv["page_size"], prefill_chunk=srv["prefill_chunk"],
        sampler=sampler, prefix_cache=bool(srv.get("prefix_cache")),
        rng_seed=engine_seed32, dp=1, overlap=bool(srv.get("overlap", True)),
        eos_id=NO_EOS)


def greedy_rows(engine, greedy_rids: set):
    """Serve the requests in ``greedy_rids`` greedily, through the
    program's own sampler at temperature 0, and the rest as the engine's
    sampler says.  The program keeps one sampler per engine; the traffic
    mixes greedy requests in so that the correctness check can compare
    their served tokens."""
    from repro.serving.sampler import SamplerConfig, sample_from_logits
    sample = engine._sample_row
    greedy = SamplerConfig(temperature=0.0)
    vocab = engine.cfg.vocab_size

    def sample_row(logits, b, req):
        if req.rid in greedy_rids:
            return int(sample_from_logits(logits[b:b + 1], greedy, vocab,
                                          req.rng)[0])
        return sample(logits, b, req)

    engine._sample_row = sample_row
