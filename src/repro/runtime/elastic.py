"""Elastic scaling: rebuild the mesh from the surviving device set and
reshard the training/serving state onto it.

Contract at fleet scale: when membership changes (node loss, pod added),
the controller picks the largest (dp', tp') grid the survivors support,
every worker restores/reshards via ``checkpoint.resharding``, and training
continues — no manual relayout.  TP changes are exact (canonicalize ->
re-scatter); DP changes only affect batch placement.

Serving-side membership changes reuse the same canonicalize -> re-scatter
shape: ``reshard_replica_pools`` maps the replica axis of every paged-cache
leaf (axis 1 by the ``paged_cache_template`` contract) from the surviving
replica indices onto a fresh pool of the new width, zero-filling joined
replicas.  ``ServingEngine.scale_to`` / ``kill_replica`` drive it.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Sequence

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding

from repro.checkpoint.resharding import reshard_params
from repro.core.partition import ShardingPlan
from repro.launch.mesh import make_mesh


@dataclass
class ElasticDecision:
    dp: int
    tp: int
    n_devices: int

    @property
    def plan(self):
        return ShardingPlan(tp=self.tp)


def choose_layout(n_devices: int, cfg, prefer_tp: int = 16) -> ElasticDecision:
    """Largest usable (dp, tp): tp <= prefer_tp, tp | n_heads-padding works
    for any tp, so the only hard constraint is tp <= n_devices."""
    tp = min(prefer_tp, n_devices)
    while n_devices % tp:
        tp -= 1
    return ElasticDecision(dp=n_devices // tp, tp=tp, n_devices=n_devices)


def rebuild(cfg, params, plan_from: ShardingPlan, devices=None,
            prefer_tp: int = 16):
    """-> (mesh, plan, resharded_params) for the current device set."""
    devices = devices if devices is not None else jax.devices()
    dec = choose_layout(len(devices), cfg, prefer_tp)
    mesh = make_mesh((dec.dp, dec.tp), ("data", "model"),
                     devices=devices[: dec.dp * dec.tp])
    plan_to = dec.plan
    new_params = reshard_params(params, cfg, plan_from, plan_to)
    return mesh, plan_to, new_params


def reshard_replica_pools(cache, keep: Sequence[int], new_n_replicas: int):
    """Re-scatter a paged serving cache onto a new replica count.

    ``keep`` lists the surviving old replica indices in their *new* order:
    survivor ``keep[j]`` becomes replica ``j`` of the new pool.  Replicas
    ``len(keep)..new_n_replicas-1`` are freshly joined and start zeroed
    (their allocators hand out pages into untouched rows, so zeroing is
    only hygiene — it matches ``zero_paged_cache_for``'s starting state).

    Every leaf of a paged cache carries the replica dimension at axis 1
    (``kvcache.paged_cache_template`` stacks replicas there for pools,
    scales, slabs, and slab scales alike), which is what lets one gather /
    scatter handle all state kinds uniformly — the serving twin of
    ``reshard_params``'s canonicalize -> re-scatter.
    """
    if not 0 < len(keep) <= new_n_replicas:
        raise ValueError(f"keep={list(keep)!r} incompatible with "
                         f"new_n_replicas={new_n_replicas}")
    keep = tuple(int(r) for r in keep)
    return jax.tree_util.tree_map(
        lambda v: _reshard_leaf(v, keep, new_n_replicas,
                                v.sharding if isinstance(v.sharding,
                                                         NamedSharding)
                                else None), cache)


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _reshard_leaf(v, keep, new_n_replicas, sharding):
    """One leaf of ``reshard_replica_pools``.  The result keeps the old
    leaf's ``NamedSharding`` (replica axis on the data axes), so each data
    shard builds only its own replicas' rows."""
    out = jnp.zeros((v.shape[0], new_n_replicas) + v.shape[2:], v.dtype)
    out = out.at[:, :len(keep)].set(
        jnp.take(v, jnp.asarray(keep, jnp.int32), axis=1))
    if sharding is not None:
        out = jax.lax.with_sharding_constraint(out, sharding)
    return out
