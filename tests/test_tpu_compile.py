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
