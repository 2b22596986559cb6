"""The system under test as the benchmark drives it: the paged ServingEngine.

``build`` makes the engine the way ``repro.launch.serve.build_engine``
does for a paged deployment (plan, mesh, sampler,
``ServingEngine.build_paged``) at the tp and dp that the configuration's
``serving`` block states, except that the weights are the benchmark's
own: drawn by ``bench/weights.py`` from the seed and laid out for the
program by ``program_params`` in the same jitted call, straight into the
shardings the program's steps expect.  How a family's weights and sizes
map onto the program is its adapter's (``bench/families/<family>.py``).
Everything else (pools, scheduler, compiled steps) is the program's.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from bench import families, weights

# Attributes the program takes as options: the harness sets them to what
# the file states before it checks the family's ``SIZES``, so that the
# program runs as the configuration says.
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
    set as the file states, and its sizes checked against the file by the
    family's ``SIZES`` (an attribute compared as a number, or a function of
    the ModelConfig compared with the file's value as it stands)."""
    from repro.configs import get_config, reduced
    cfg = get_config(cfgfile["arch"])
    if cfgfile.get("smoke"):
        cfg = reduced(cfg)
    sizes = families.load(cfgfile["reference"]).SIZES
    cfg = dataclasses.replace(cfg, **{
        attr: type(getattr(cfg, attr))(stated(cfgfile, key))
        for key, attr in sizes.items()
        if isinstance(attr, str) and attr in OPTIONS})
    for key, attr in sizes.items():
        if callable(attr):
            if attr(cfg) != cfgfile[key]:
                raise SystemExit(f"bench: the program's {cfg.name} gives "
                                 f"{key} = {attr(cfg)!r}, the configuration "
                                 f"file states {cfgfile[key]!r}")
        elif float(getattr(cfg, attr)) != float(stated(cfgfile, key)):
            raise SystemExit(f"bench: the program's {cfg.name}.{attr} = "
                             f"{getattr(cfg, attr)}, the configuration "
                             f"file states {key} = {cfgfile[key]}")
    return cfg


def _paths(tree, prefix=()) -> dict:
    """{path: leaf} of a nested dict; a path is the tuple of its keys."""
    if not isinstance(tree, dict):
        return {prefix: tree}
    return {p: v for k, sub in tree.items()
            for p, v in _paths(sub, prefix + (k,)).items()}


def _nest(flat: dict) -> dict:
    """The nested dict of ``{path: leaf}``."""
    out = {}
    for path, v in flat.items():
        node = out
        for k in path[:-1]:
            node = node.setdefault(k, {})
        node[path[-1]] = v
    return out


def layer_holders(cfg, tmpl) -> dict:
    """{layer path: the global indices of the program's layers that hold
    it, in layer order}.  Row r of a canonical layer tensor belongs to the
    r-th of them; in a uniform stack row r is layer r."""
    holders, offset = {}, 0
    for group, gt in zip(cfg.layer_groups(), tmpl["stacks"], strict=True):
        plen = len(group.pattern)
        for i in range(group.n_layers):
            for path in _paths(gt[i % plen]):
                holders.setdefault(path, []).append(offset + i)
        offset += group.n_layers
    return holders


def program_params(cfg, plan, mesh, fam: str, spec: dict, seed32: int):
    """-> the program's parameter tree holding the canonical weights of
    ``spec`` for ``seed32``, made in one jitted call into the shardings of
    ``model.param_pspecs``, laid out by the family's adapter."""
    from jax.sharding import NamedSharding

    from repro.core import model
    from repro.core.partition import model_layout
    lay = model_layout(cfg, plan)
    tmpl = model.model_template(cfg)
    abstract = model.abstract_params(cfg, plan)
    pspecs = model.param_pspecs(cfg, plan)
    adapter = families.load(fam)
    by_path = {v: k for k, v in adapter.LAYOUT["layer"].items()}
    top = {v: k for k, v in adapter.LAYOUT["top"].items()}
    holders = layer_holders(cfg, tmpl)
    for path, layers in holders.items():
        name = by_path.get(path)
        if name is None or name not in spec:
            raise SystemExit(f"bench: the program's layer weight "
                             f"{'/'.join(path)} has no canonical weight "
                             f"(bench/families/{fam}.py names {name!r})")
        if spec[name][0][0] != len(layers):
            raise SystemExit(f"bench: canonical weight {name} has "
                             f"{spec[name][0][0]} rows; the program holds "
                             f"{'/'.join(path)} in {len(layers)} layers")
    used = set()

    def convert(name, full, pspec, want):
        if name in adapter.OFFSET_NORMS:
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
        canon = weights.generate(spec, key, fam)
        want_top = _paths({k: v for k, v in abstract.items()
                           if k != "stacks"})
        out = {}
        for path, ps in _paths({k: v for k, v in tmpl.items()
                                if k != "stacks"}).items():
            name = top[path]
            used.add(name)
            out[path] = convert(name, canon[name].reshape(ps.full), ps,
                                want_top[path])
        out = _nest(out)
        stacks, offset = [], 0
        for g, (group, gt) in enumerate(zip(cfg.layer_groups(),
                                            tmpl["stacks"], strict=True)):
            plen = len(group.pattern)
            per_pat = []
            for pi, lt in enumerate(gt):
                layers = offset + pi + plen * np.arange(group.n_reps)
                want_lt = _paths(abstract["stacks"][g][pi])
                node = {}
                for path, ps in _paths(lt).items():
                    name = by_path[path]
                    used.add(name)
                    rows = np.searchsorted(holders[path], layers)
                    full = canon[name][rows].reshape(
                        (group.n_reps,) + tuple(ps.full))
                    want = want_lt[path]
                    node[path] = jax.vmap(
                        lambda f, ps=ps, name=name, want=want: convert(
                            name, f, ps, jax.ShapeDtypeStruct(
                                want.shape[1:], want.dtype)))(full)
                per_pat.append(_nest(node))
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
    tp, dp = int(srv["tp"]), int(srv["dp"])
    plan = ShardingPlan(tp=tp, kv_cache_dtype=srv["kv_dtype"],
                        ssm_cache_dtype=srv.get("ssm_state_dtype", ""))
    mesh = host_mesh(tp=tp, dp=dp)
    params = program_params(cfg, plan, mesh, cfgfile["reference"], spec,
                            seed32)
    samp = mix.get("sampling", {})
    sampler = SamplerConfig(temperature=float(samp.get("temperature", 0.0)),
                            top_k=int(samp.get("top_k", 0)))
    return ServingEngine.build_paged(
        cfg, plan, mesh, cell["slots"], cell["seq_budget"], params,
        page_size=srv["page_size"], prefill_chunk=srv["prefill_chunk"],
        sampler=sampler, prefix_cache=bool(srv.get("prefix_cache")),
        rng_seed=engine_seed32, dp=dp, overlap=bool(srv.get("overlap", True)),
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
