"""Compile-only checks for the TPU v5e: the served path's compiled steps
and the Pallas paged-attention kernels, built for a described (not
attached) ``v5e:2x2`` topology by the TPU compiler installed with JAX.

Interpret mode and the CPU backend accept programs the chip's compiler
refuses (block tiling, VMEM limits, device memory).  These tests compile
for the chip without one: nothing runs, so they say nothing about results
or times.  The topology is described inside a fixture — never at import —
because only one process may load the TPU library at a time; the tests
stay in this one file so a single worker loads it.
"""
import dataclasses
import os
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding

from repro.configs import get_config
from repro.core import model, steps
from repro.core.partition import ShardingPlan
from repro.kernels.decode_attention import (paged_decode_attention,
                                            paged_verify_attention)

V5E_HBM_BYTES = 16 * 10**9
# qwen3-0.6b at its published widths; two layers keep each compile to a
# few seconds (layers are scanned, so depth barely changes the program)
SLOTS, BUDGET, PSZ, CHUNK, DRAFT_Q = 8, 2048, 16, 128, 4


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a compile for a described chip cannot be read back from the
    # persistent cache without one: keep these compiles out of it
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def chip_mesh(topo):
    return Mesh(np.array(topo.devices[:1]).reshape(1, 1), ("data", "model"),
                axis_types=(jax.sharding.AxisType.Auto,) * 2)


def _on(mesh, tree, specs):
    return jax.tree_util.tree_map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                          sharding=NamedSharding(mesh, s)),
        tree, specs)


def _step(kind, cfg, plan, mesh):
    """-> (step fn, argument names after (params, cache)) for one paged
    step family member at the engine's shapes."""
    n_max = BUDGET // PSZ
    n_pages = SLOTS * n_max + 1
    if kind == "decode":
        fn, t, s = steps.make_paged_decode_step(cfg, plan, mesh, SLOTS,
                                                n_pages, PSZ, n_max)
        return fn, t, s, ("tokens1", "pos", "block_table")
    if kind == "prefill_chunk":
        fn, t, s = steps.make_prefill_chunk_step(cfg, plan, mesh, CHUNK,
                                                 n_pages, PSZ, n_max)
        return fn, t, s, ("tokens", "chunk_start", "last_idx",
                          "block_table")
    fn, t, s = steps.make_verify_step(cfg, plan, mesh, SLOTS, DRAFT_Q,
                                      n_pages, PSZ, n_max)
    return fn, t, s, ("tokens", "pos", "qlen", "block_table")


@pytest.mark.parametrize("kind", ["decode", "prefill_chunk", "verify"])
def test_paged_step_compiles_for_v5e(chip_mesh, kind):
    cfg = dataclasses.replace(get_config("qwen3-0.6b"), n_layers=2)
    plan = ShardingPlan(tp=1, kv_cache_dtype="bfloat16")
    fn, t, s, names = _step(kind, cfg, plan, chip_mesh)
    args = [_on(chip_mesh, model.abstract_params(cfg, plan),
                model.param_pspecs(cfg, plan)),
            _on(chip_mesh, t["cache"], s["cache"])]
    args += [_on(chip_mesh, t[k], s[k]) for k in names]
    with chip_mesh:
        compiled = jax.jit(fn, donate_argnums=(1,)).lower(*args).compile()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert 0 < used < V5E_HBM_BYTES, used
    # the cache is donated: the step updates the pools in place
    assert mem.alias_size_in_bytes > 0


# the benchmark's deployments: qwen3-0.6b with 16 slots of 3072 tokens in
# 16-token pages (3073 pages), mamba2-370m with 64 slots (65 slabs), at
# their published depth
CELL = {"qwen3-0.6b": dict(slots=16, n_pages=3073, n_slabs=0),
        "mamba2-370m": dict(slots=64, n_pages=2, n_slabs=65)}
CELL_BUDGET = 3072


def _pool_sized(text, cache, min_bytes):
    """HLO instructions of ``text`` that copy or allocate a whole stacked
    pool: a ``copy`` (or a fusion named for one) or an ``AllocateBuffer``
    whose dims, size-1 dims aside, are those of a cache leaf of at least
    ``min_bytes``."""
    def key(dims):
        return tuple(sorted(d for d in dims if d != 1))

    pools = {key(leaf.shape) for leaf in jax.tree_util.tree_leaves(cache)
             if leaf.size * leaf.dtype.itemsize >= min_bytes}
    out = []
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%?([\w.\-]+) = \w+\[([\d,]*)\]", line)
        if not m or ("copy" not in m.group(1)
                     and "AllocateBuffer" not in line):
            continue
        if key(int(d) for d in m.group(2).split(",") if d) in pools:
            out.append(line.strip()[:160])
    return out


@pytest.mark.parametrize("name,kind,dtype", [
    ("qwen3-0.6b", kind, dtype)
    for kind in ("decode", "prefill_chunk", "verify")
    for dtype in ("bfloat16", "int8")] + [
    ("mamba2-370m", kind, "bfloat16")
    for kind in ("decode", "prefill_chunk")])
def test_paged_step_updates_pools_in_place_on_v5e(chip_mesh, name, kind,
                                                  dtype):
    """The layer loop carries the stacked pools and writes only the rows it
    addresses: at the benchmark's deployment sizes no compiled step copies
    or allocates a whole pool, and its scratch memory stays a small share
    of the donated cache (a step that re-stacked the pools as scan outputs
    needed more temp than the pools themselves)."""
    cfg = get_config(name)
    plan = ShardingPlan(tp=1, kv_cache_dtype=dtype)
    geo = CELL[name]
    n_max = CELL_BUDGET // PSZ
    if kind == "decode":
        fn, t, s = steps.make_paged_decode_step(
            cfg, plan, chip_mesh, geo["slots"], geo["n_pages"], PSZ, n_max,
            n_slabs=geo["n_slabs"])
        names = ("tokens1", "pos", "block_table")
    elif kind == "prefill_chunk":
        fn, t, s = steps.make_prefill_chunk_step(
            cfg, plan, chip_mesh, CHUNK, geo["n_pages"], PSZ, n_max,
            n_slabs=geo["n_slabs"])
        names = ("tokens", "chunk_start", "last_idx", "block_table")
    else:
        fn, t, s = steps.make_verify_step(cfg, plan, chip_mesh, geo["slots"],
                                          DRAFT_Q, geo["n_pages"], PSZ, n_max)
        names = ("tokens", "pos", "qlen", "block_table")
    if geo["n_slabs"]:
        names += ("slab_ids",)
    args = [_on(chip_mesh, model.abstract_params(cfg, plan),
                model.param_pspecs(cfg, plan)),
            _on(chip_mesh, t["cache"], s["cache"])]
    args += [_on(chip_mesh, t[k], s[k]) for k in names]
    with chip_mesh:
        compiled = jax.jit(fn, donate_argnums=(1,)).lower(*args).compile()
    cache_bytes = sum(leaf.size * leaf.dtype.itemsize
                      for leaf in jax.tree_util.tree_leaves(t["cache"]))
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= cache_bytes     # donated, tiles padded
    assert mem.temp_size_in_bytes < 0.1 * cache_bytes, \
        (mem.temp_size_in_bytes, cache_bytes)
    # the int8 scale side tensors (0.2 % of the cache) may be staged whole
    # in the core's fast memory for the loop; every larger pool stays put
    assert not _pool_sized(compiled.as_text(), t["cache"],
                           0.01 * cache_bytes)


@pytest.mark.parametrize("dtype", ["bfloat16", "int8"])
@pytest.mark.parametrize("kernel", ["decode", "verify"])
def test_paged_kernel_compiles_for_v5e(one_chip, kernel, dtype):
    # qwen3-0.6b's KV heads and head dim; the engine's page geometry
    B, H, D = SLOTS, 8, 128
    n_max = BUDGET // PSZ
    n_pages = B * n_max + 1

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    q = sds((B, H, D) if kernel == "decode" else (B, H, DRAFT_Q, D),
            jnp.bfloat16)
    pages = sds((n_pages, H, PSZ, D), jnp.dtype(dtype))
    args = [q, pages, pages, sds((B, n_max), jnp.int32), sds((B,), jnp.int32)]
    fn = paged_decode_attention if kernel == "decode" \
        else paged_verify_attention
    if dtype == "int8":
        args += [sds((n_pages, PSZ), jnp.float32)] * 2

        def call(q, k, v, bt, ln, ks, vs):
            return fn(q, k, v, bt, ln, k_scale=ks, v_scale=vs)
    else:
        call = fn
    compiled = jax.jit(call).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("kind", ["decode", "prefill_chunk"])
def test_nemotron_cell_steps_fit_v5e(chip_mesh, kind):
    """The nemotron3-reason deployment (128 slots of 4096 tokens: 129
    state slabs in the nine Mamba-2 layers, 32,769 pages in the three
    attention layers, 16 experts in each of nine expert layers) compiles
    for one v5e, fits its 16 GB, and copies no whole pool."""
    cfg = get_config("nemotron3-nano-ep8")
    plan = ShardingPlan(tp=1)
    slots, budget = 128, 4096
    n_max = budget // PSZ
    geo = dict(n_pages=slots * n_max + 1, page_size=PSZ, n_max_pages=n_max,
               n_slabs=slots + 1)
    if kind == "decode":
        fn, t, s = steps.make_paged_decode_step(cfg, plan, chip_mesh, slots,
                                                **geo)
        names = ("tokens1", "pos", "block_table", "slab_ids")
    else:
        fn, t, s = steps.make_prefill_chunk_step(cfg, plan, chip_mesh, CHUNK,
                                                 **geo)
        names = ("tokens", "chunk_start", "last_idx", "block_table",
                 "slab_ids")
    args = [_on(chip_mesh, model.abstract_params(cfg, plan),
                model.param_pspecs(cfg, plan)),
            _on(chip_mesh, t["cache"], s["cache"])]
    args += [_on(chip_mesh, t[k], s[k]) for k in names]
    with chip_mesh:
        compiled = jax.jit(fn, donate_argnums=(1,)).lower(*args).compile()
    cache_bytes = sum(leaf.size * leaf.dtype.itemsize
                      for leaf in jax.tree_util.tree_leaves(t["cache"]))
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert mem.alias_size_in_bytes >= cache_bytes     # donated
    assert used < 0.75 * V5E_HBM_BYTES, used
    assert not _pool_sized(compiled.as_text(), t["cache"],
                           0.01 * cache_bytes)


@pytest.mark.parametrize("rows, vocab, v_pad", [(128, 131072, 131072),
                                                (6, 151936, 151936)])
def test_greedy_ids_compile_for_v5e(one_chip, rows, vocab, v_pad):
    """The greedy pick of the nemotron3-reason decode step (128 rows of
    131,072) and of qwen3-docs (6 of 151,936) compiles for one v5e and
    reduces the bf16 logits without a copy of them."""
    x = jax.ShapeDtypeStruct((rows, v_pad), jnp.bfloat16, sharding=one_chip)
    compiled = steps.greedy_ids.lower(x, vocab).compile()
    mem = compiled.memory_analysis()
    # int32 ids, padded at most to a tile of the output
    assert rows * 4 <= mem.output_size_in_bytes <= max(rows * 4, 4096)
    assert mem.temp_size_in_bytes < rows * v_pad * 2 // 4, \
        mem.temp_size_in_bytes
