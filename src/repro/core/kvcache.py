"""Per-layer decode caches (KV, SSM state, cross-attention KV).

Global (pre-shard_map) layouts — heads carry an explicit tp*local dim
sharded on the model axis, mirroring the weight convention:

    kv.k / kv.v : (reps, B, tp * n_kv_loc, W, D)    W = window or seq budget
    kv.pos      : (reps, B, W)  absolute position per slot (-1 = empty);
                  ring-indexed (pos % W) for windowed layers
    ssm.state   : (reps, B, tp * h_loc, P, N)  float32
    ssm.conv_*  : (reps, B, K-1, channels)
    cross.k/v   : (reps, B, tp * n_kv_loc, S_enc, D)

For ``plan.seq_shard_kv`` (long-context decode) the W dim is additionally
sharded over the data axes — each data shard holds a contiguous slice of the
sequence and attention merges partials via LSE psums (attention.py).

Paged-serving clauses (machine-checked by scripts/check_static.py; the
block-pool layouts are further down):

Invariant: one static allocation — every pool/slab is a fixed array
    whose placement never changes; request lengths appear only as data
    (block tables, positions, slab ids), never as shapes.
Enforced-by: tests/test_paged_cache.py::test_paged_engine_matches_contiguous_greedy, analysis:jit-stability

Invariant: page 0 / slab 0 are scratch — idle decode lanes point their
    block tables (and slab ids) at the reserved index so the fused
    decode step always runs full-batch; scratch contents are garbage by
    convention and must never be read back.
Enforced-by: tests/test_paged_cache.py::test_paged_steps_match_contiguous_mixed_lengths

Invariant: refcounts own pages — a page returns to the free list exactly
    when its last reference drops (slot block-table entries,
    radix-prefix-cache nodes and cross-KV cache entries each hold one
    ref per page).  Shared pages are immutable; divergence goes through
    a copy-on-write duplicate.
Enforced-by: tests/test_paged_cache.py::test_page_allocator_reuse_and_exhaustion, analysis:refcount-leak, analysis:shared-free, analysis:allocator-internals

Invariant: slabs are exclusive — recurrent SSM state cannot be shared or
    re-derived from pages, so a slab has exactly one owner, is zeroed on
    allocation, and is snapshot/restored through the engine's host-side
    stash across preemption (``serving.engine``).
Enforced-by: tests/test_paged_cache.py::test_ssm_int8_forced_preemption_identity

Invariant: page handoff is an atomic ref transfer — moving a request's
    resident pages to another replica (``handoff_refs``) drops the source
    slot's references exactly once and only after verifying every
    destination page is freshly allocated (refcount 1, no inherited
    sharers); a page is never referenced by two replicas' allocators.
Enforced-by: tests/test_page_transfer.py::test_handoff_refs_decrefs_source_once, analysis:refcount-leak
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs.base import ATTN_WINDOW, ModelConfig


def kv_window(cfg: ModelConfig, spec, budget: int) -> int:
    if spec.attn == ATTN_WINDOW and cfg.sliding_window:
        return min(budget, cfg.sliding_window)
    return budget


def layer_cache_template(cfg, plan, lay, spec, batch: int, budget: int,
                         seq_sharded: bool, batch_sharded: bool = True):
    """-> dict of (shape, dtype, pspec) triples for ONE layer (no reps dim)."""
    out = {}
    kvd = jnp.dtype(plan.kv_cache_dtype)
    d = cfg.head_dim_
    batch_axes = tuple(plan.dp_axes) if (batch_sharded and not seq_sharded) \
        else None
    seq_axes = tuple(plan.dp_axes) if seq_sharded else None
    tpax = "model" if plan.tp > 1 else None   # head dims follow TP only
    if "kv" in spec.cache_kinds():
        W = kv_window(cfg, spec, budget)
        wseq = seq_axes if (seq_sharded and W == budget) else None
        out["kv"] = {
            "k": ((batch, plan.tp * lay.attn.n_kv_loc, W, d), kvd,
                  P(batch_axes, tpax, wseq, None)),
            "v": ((batch, plan.tp * lay.attn.n_kv_loc, W, d), kvd,
                  P(batch_axes, tpax, wseq, None)),
            "pos": ((batch, W), jnp.int32, P(batch_axes, wseq)),
        }
    if "ssm" in spec.cache_kinds():
        H, Pdim, N = lay.ssm.hq_loc, cfg.ssm_head_dim, cfg.ssm_state
        K = cfg.ssm_conv
        cx = plan.tp * H * Pdim
        out["ssm"] = {
            "state": ((batch, plan.tp * H, Pdim, N), jnp.float32,
                      P(batch_axes, tpax, None, None)),
            "conv_x": ((batch, K - 1, cx), jnp.dtype(cfg.dtype),
                       P(batch_axes, None, tpax)),
            "conv_B": ((batch, K - 1, N), jnp.dtype(cfg.dtype),
                       P(batch_axes, None, None)),
            "conv_C": ((batch, K - 1, N), jnp.dtype(cfg.dtype),
                       P(batch_axes, None, None)),
        }
    if "cross_kv" in spec.cache_kinds():
        S_enc = cfg.enc_seq_len
        out["cross"] = {
            "k": ((batch, plan.tp * lay.attn.n_kv_loc, S_enc, d), kvd,
                  P(batch_axes, tpax, None, None)),
            "v": ((batch, plan.tp * lay.attn.n_kv_loc, S_enc, d), kvd,
                  P(batch_axes, tpax, None, None)),
        }
    return out


def cache_template(cfg, plan, lay, batch: int, budget: int,
                   batch_sharded: bool = True):
    """Full cache: list (per layer group) of stacked templates."""
    seq_sharded = plan.seq_shard_kv
    groups = cfg.layer_groups()
    tmpl = []
    for g in groups:
        per_pattern = []
        for spec in g.pattern:
            t = layer_cache_template(cfg, plan, lay, spec, batch, budget,
                                     seq_sharded, batch_sharded)
            per_pattern.append(_stack_template(t, g.n_reps))
        tmpl.append(per_pattern)
    return tmpl


def _stack_template(t, reps):
    return jax.tree_util.tree_map(
        lambda trip: ((reps,) + trip[0], trip[1], P(*((None,) + tuple(trip[2])))),
        t, is_leaf=lambda x: isinstance(x, tuple) and len(x) == 3
        and isinstance(x[0], tuple))


def abstract_cache(tmpl):
    def mk(trip):
        shape, dtype, _ = trip
        return jax.ShapeDtypeStruct(shape, dtype)
    return _map_tmpl(tmpl, mk)


def cache_pspecs(tmpl):
    return _map_tmpl(tmpl, lambda trip: trip[2])


def zero_cache(tmpl, mesh):
    """Empty cache, each leaf created directly in its shards on ``mesh``:
    no device ever holds a whole array."""
    def mk(trip):
        shape, dtype, spec = trip
        dev = NamedSharding(mesh, spec)
        if dtype == jnp.int32:       # pos slots start empty
            return jnp.full(shape, -1, dtype, device=dev)
        return jnp.zeros(shape, dtype, device=dev)
    return _map_tmpl(tmpl, mk)


def _map_tmpl(tmpl, fn):
    return jax.tree_util.tree_map(
        fn, tmpl,
        is_leaf=lambda x: isinstance(x, tuple) and len(x) == 3
        and isinstance(x[0], tuple))


# ---------------------------------------------------------------------------
# Paged KV cache (vLLM-style block pool)
# ---------------------------------------------------------------------------
#
# Instead of one exact-length lane per slot, K/V live in a fixed pool of
# fixed-size pages; each serving slot owns a host-managed list of page ids
# (its block table).  Token t of a slot lives at page block_table[t // psz],
# offset t % psz.  This keeps the paper's residency discipline — the pool is
# one static allocation whose placement never changes — while letting a
# single compiled decode/prefill-chunk pair serve arbitrary request mixes.
#
# Pool layout keeps the contiguous convention with the page pool standing in
# for the batch dim:   kp / vp : (reps, n_replicas, n_pages, tp*n_kv_loc,
# psz, D) sharded P(None, dp_axes, None, tpax, None, None): heads follow
# TP, and the leading replica dim is sharded over the data axes so each
# data shard holds only its own replicas' pages — the paper's
# stationary-local-memory discipline at serving scale.  Block tables stay
# replica-relative (ids in [0, n_pages)); ``core.steps`` folds each
# shard's local replicas into one larger pool and offsets the tables
# row-wise, so attention/kernels never see the replica dim.  With
# n_replicas == 1 (the default) the layout degenerates to the old
# replicated-pool dp=1 behavior.
#
# Page 0 of every replica is reserved as a scratch page: idle decode lanes
# point their block tables at it, so the fused decode step can always run
# full-batch without masking writes.

SCRATCH_PAGE = 0
SCRATCH_SLAB = 0


def kv_pool_is_quantized(plan) -> bool:
    """True when the paged self-KV / cross-KV pools store int8 payloads with
    per-(page, slot) float scales (``plan.kv_cache_dtype == "int8"``)."""
    return jnp.dtype(plan.kv_cache_dtype) == jnp.int8


def ssm_pool_is_quantized(plan) -> bool:
    """True when the SSM state slabs store int8 payloads with per-(slab,
    head) float scales (``plan.ssm_cache_dtype == "int8"``)."""
    return bool(plan.ssm_cache_dtype) and \
        jnp.dtype(plan.ssm_cache_dtype) == jnp.int8


def cache_profile(cfg) -> set:
    """Union of decode-cache kinds across the decoder stack:
    subset of {"kv", "ssm", "cross_kv"}."""
    kinds = set()
    for spec in cfg.layer_specs():
        kinds.update(spec.cache_kinds())
    return kinds


def paged_cache_supported(cfg) -> tuple:
    """-> (ok, reason).  Paged serving covers every decode-capable arch
    whose serving inputs are tokens (+ encoder frames): attention-only and
    hybrid/SSM decoders page (or slab) their self state, and enc-dec
    decoders page the encoder memory's cross-KV."""
    if cfg.is_encoder_only:
        return False, "encoder-only arch has no decode path to serve"
    if cfg.frontend == "vision_patches":
        return False, ("vision frontend needs image-embed injection at "
                       "prefill; the token-only chunked prefill step "
                       "cannot carry it")
    return True, ""


def paged_cache_template(cfg, plan, lay, n_pages: int, page_size: int,
                         n_replicas: int = 1, n_slabs: int = 0):
    """Full paged cache template: list (per layer group) of stacked pools.

    Per layer, by cache kind:

    * ``kv``    — ``kp``/``vp`` page pools (block-table indirection),
    * ``ssm``   — ``statep``/``conv_xp``/``conv_Bp``/``conv_Cp`` slab
      pools: ``n_slabs`` rows of per-request recurrent state, read/written
      by slot-relative slab id (no paging — SSD state is O(1) per request
      and cannot be shared),
    * ``cross_kv`` — ``ckp``/``cvp`` page pools holding the encoder
      memory's K/V.  Cross pages share the self-KV page-id space (one
      allocator covers both) and are immutable after the encode-time
      write, so they are shared by refcount alone — no copy-on-write.

    ``n_replicas`` adds a leading replica dim sharded over ``plan.dp_axes``
    — each data shard stores only its replicas' pages/slabs (dp>1
    serving).

    **Quantized pools** (``plan.kv_cache_dtype == "int8"`` /
    ``plan.ssm_cache_dtype == "int8"``): payloads store int8 and each pool
    gains a small float32 scale side tensor — ``ksp``/``vsp`` (and
    ``cksp``/``cvsp`` for cross) of shape (n_replicas, n_pages, page_size),
    one scale per (page, token slot) so every token row is quantized
    independently of write order (schedule/speculation/preemption
    invariance by construction); ``sscalep`` of shape (n_replicas,
    n_slabs, tp*H), one scale per (slab, head), re-written wholesale on
    every state scatter.  A zero scale dequantizes to exact zeros, so
    ``zero_paged_cache`` leaves the quantized pools indistinguishable
    from zeroed fp pools.  Float dtypes produce the exact pre-quantization
    templates — no scale leaves exist."""
    ok, why = paged_cache_supported(cfg)
    if not ok:
        raise ValueError(f"paged cache unsupported for {cfg.name}: {why}")
    assert n_replicas >= 1, n_replicas
    kvd = jnp.dtype(plan.kv_cache_dtype)
    kv_quant = kv_pool_is_quantized(plan)
    d = cfg.head_dim_
    tpax = "model" if plan.tp > 1 else None
    dpax = tuple(plan.dp_axes)
    pool = ((n_replicas, n_pages, plan.tp * lay.attn.n_kv_loc, page_size, d),
            kvd, P(dpax, None, tpax, None, None))
    scale = ((n_replicas, n_pages, page_size), jnp.float32,
             P(dpax, None, None))
    slab = None
    if "ssm" in cache_profile(cfg):
        assert n_slabs > 1, f"ssm layers need n_slabs > 1, got {n_slabs}"
        H, Pdim, N = lay.ssm.hq_loc, cfg.ssm_head_dim, cfg.ssm_state
        K = cfg.ssm_conv
        sd = jnp.int8 if ssm_pool_is_quantized(plan) else jnp.float32
        slab = {
            "statep": ((n_replicas, n_slabs, plan.tp * H, Pdim, N),
                       sd, P(dpax, None, tpax, None, None)),
            "conv_xp": ((n_replicas, n_slabs, K - 1, plan.tp * H * Pdim),
                        jnp.dtype(cfg.dtype), P(dpax, None, None, tpax)),
            "conv_Bp": ((n_replicas, n_slabs, K - 1, N), jnp.dtype(cfg.dtype),
                        P(dpax, None, None, None)),
            "conv_Cp": ((n_replicas, n_slabs, K - 1, N), jnp.dtype(cfg.dtype),
                        P(dpax, None, None, None)),
        }
        if sd == jnp.int8:
            slab["sscalep"] = ((n_replicas, n_slabs, plan.tp * H),
                               jnp.float32, P(dpax, None, tpax))
    tmpl = []
    for g in cfg.layer_groups():
        per_pattern = []
        for spec in g.pattern:
            kinds = spec.cache_kinds()
            t = {}
            if "kv" in kinds:
                t["kv"] = {"kp": pool, "vp": pool}
                if kv_quant:
                    t["kv"]["ksp"] = scale
                    t["kv"]["vsp"] = scale
            if "ssm" in kinds:
                t["ssm"] = dict(slab)
            if "cross_kv" in kinds:
                t["cross"] = {"ckp": pool, "cvp": pool}
                if kv_quant:
                    t["cross"]["cksp"] = scale
                    t["cross"]["cvsp"] = scale
            per_pattern.append(_stack_template(t, g.n_reps))
        tmpl.append(per_pattern)
    return tmpl


def fold_replica_pools(cache):
    """(reps, R_loc, n_pages, G, psz, D) -> (reps, R_loc*n_pages, G, psz, D).

    Per-shard view: the shard's local replicas become one larger pool, so
    the attention gather/scatter path is replica-agnostic.  Replica ``i``'s
    page ``p`` lives at folded id ``i * n_pages + p`` (see
    ``replica_table_offsets``)."""
    return jax.tree_util.tree_map(
        lambda pool: pool.reshape((pool.shape[0],
                                   pool.shape[1] * pool.shape[2])
                                  + pool.shape[3:]), cache)


def unfold_replica_pools(cache, n_replicas_loc: int):
    """Inverse of ``fold_replica_pools``."""
    return jax.tree_util.tree_map(
        lambda pool: pool.reshape(
            (pool.shape[0], n_replicas_loc, pool.shape[1] // n_replicas_loc)
            + pool.shape[2:]), cache)


def zero_paged_cache(tmpl, mesh):
    return _map_tmpl(tmpl, lambda trip: jnp.zeros(
        trip[0], trip[1], device=NamedSharding(mesh, trip[2])))


class PageAllocator:
    """Host-side refcounted block-pool allocator (page 0 reserved as scratch).

    All-or-nothing allocation: a request either gets every page it needs up
    front (prompt + max_new_tokens worth) or stays queued — admission control
    instead of mid-flight OOM.  Freed pages return to the pool LIFO, so a
    steady-state request mix reuses a small working set.

    Pages carry a reference count so they can be shared: a freshly allocated
    page has one owner; the prefix cache and additional serving slots take
    extra refs via ``incref``.  A page returns to the free list only when its
    last ref drops (``decref``; ``free`` is a synonym for the sole-owner
    case).  Shared pages are immutable by convention — a slot that must
    append into one first takes a private copy (copy-on-write; see
    ``serving.prefix_cache``).

    Quantized pools additionally track **scale-dirty** pages: every page
    whose last ref drops (via ``decref`` — ``free`` and the speculative
    ``trim`` both funnel through it) is marked so the engine can zero its
    per-slot scale rows before the page is recycled, guaranteeing a
    recycled page never pairs stale scales with fresh payloads
    (``take_scale_dirty``)."""

    def __init__(self, n_pages: int, n_reserved: int = 1):
        assert n_pages > n_reserved, (n_pages, n_reserved)
        self.n_pages = n_pages
        self.n_reserved = n_reserved
        self._free = list(range(n_pages - 1, n_reserved - 1, -1))
        self._free_set = set(self._free)     # O(1) double-free detection
        self._rc = [0] * n_pages
        self._scale_dirty: set = set()       # freed pages w/ stale scale rows
        self.total_allocated = 0             # pages ever handed out (stats)
        self.pages_transferred_out = 0       # handed to another replica
        self.pages_transferred_in = 0        # received from another replica

    @property
    def n_free(self) -> int:
        return len(self._free)

    def refcount(self, page: int) -> int:
        return self._rc[page]

    def alloc(self, n: int):
        """-> list of n page ids (each refcount 1), or None if the pool
        can't cover n."""
        if n > len(self._free):
            return None
        out = [self._free.pop() for _ in range(n)]
        self._free_set.difference_update(out)
        for p in out:
            self._rc[p] = 1
        self.total_allocated += n
        return out

    def incref(self, pages):
        """Add one ref per page (sharing an already-live page)."""
        for p in pages:
            assert self._rc[p] > 0, f"incref of unallocated page {p}"
            self._rc[p] += 1

    def decref(self, pages):
        """Drop one ref per page; pages whose last ref drops are freed."""
        for p in pages:
            assert p >= self.n_reserved, f"freeing reserved page {p}"
            assert p not in self._free_set, f"double free of page {p}"
            self._rc[p] -= 1
            if self._rc[p] == 0:
                self._free.append(p)
                self._free_set.add(p)
                self._scale_dirty.add(p)

    def free(self, pages):
        """Release sole-owner pages.  Errors on a shared page: silently
        dropping one of several refs here would hand a prefix-cache- or
        slot-shared page back to the free list while it is still mapped —
        use ``decref`` for the multi-ref case."""
        for p in pages:
            assert self._rc[p] == 1, \
                f"free() of shared page {p} (refcount {self._rc[p]}); " \
                f"multi-ref releases must go through decref()"
        self.decref(pages)

    def trim(self, pages):
        """Release a slot's *tail* pages while the slot stays live (the
        speculative-decoding rollback path: draft-headroom pages past the
        block-table keep point).  Unlike ``free``, a trimmed page may
        legitimately be shared by the time the trim runs — a preemption
        donated the slot's resident pages to the prefix cache, or another
        admission mapped them — so trim drops exactly the slot's own
        reference and the page returns to the pool only when its last
        sharer lets go."""
        self.decref(pages)

    def take_scale_dirty(self) -> list:
        """Drain the pages needing a scale reset before reuse: every page
        freed (last ref dropped) since the previous drain that is still on
        the free list.  A dirty page meanwhile re-allocated stays marked —
        resetting it mid-flight would corrupt the new occupant, and its
        stale rows are benign until it is freed again (per-slot scales are
        rewritten atomically with every payload write, and un-rewritten
        slots sit beyond the occupant's length mask)."""
        out = sorted(self._scale_dirty & self._free_set)
        self._scale_dirty.difference_update(out)
        return out


class SlabAllocator:
    """Host-side free-list allocator for SSM state slabs (slab 0 scratch).

    A slab holds one request's recurrent state (SSD ``state`` plus conv
    tails) across every SSM/hybrid layer.  Unlike pages, slabs are never
    shared — recurrent state has exactly one owner and cannot be re-derived
    from donated pages — so there are no refcounts: ``alloc`` hands out one
    slab id (or None when exhausted, for all-or-nothing admission) and
    ``free`` returns it.  The engine zeroes a slab at allocation and
    snapshot/restores it through a host-side stash across preemption."""

    def __init__(self, n_slabs: int, n_reserved: int = 1):
        assert n_slabs > n_reserved, (n_slabs, n_reserved)
        self.n_slabs = n_slabs
        self.n_reserved = n_reserved
        self._free = list(range(n_slabs - 1, n_reserved - 1, -1))
        self.total_allocated = 0

    @property
    def n_free(self) -> int:
        return len(self._free)

    def alloc(self):
        """-> one slab id, or None when the pool is exhausted."""
        if not self._free:
            return None
        self.total_allocated += 1
        return self._free.pop()

    def free(self, slab: int):
        assert slab >= self.n_reserved, f"freeing reserved slab {slab}"
        assert slab not in self._free, f"double free of slab {slab}"
        self._free.append(slab)


def pages_needed(n_tokens: int, page_size: int) -> int:
    return -(-n_tokens // page_size)


def handoff_refs(src_alloc: PageAllocator, src_pages,
                 dst_alloc: PageAllocator, dst_pages):
    """Atomically move ownership of a page run between replica allocators.

    The destination side allocates fresh pages up front (``dst_pages``,
    each refcount 1 — the device transfer copies payload bytes into them);
    this bookkeeping step then drops the source slot's references exactly
    once.  Source pages that the radix prefix cache (or another slot) still
    shares simply lose one ref and stay resident on the source replica —
    the handoff never frees a shared page out from under its sharers.
    """
    assert src_alloc is not dst_alloc, "handoff within one replica"
    assert len(src_pages) == len(dst_pages), (src_pages, dst_pages)
    for p in dst_pages:
        assert dst_alloc.refcount(p) == 1, \
            f"handoff into shared destination page {p} " \
            f"(refcount {dst_alloc.refcount(p)}); destination pages must " \
            f"be freshly allocated"
    src_alloc.decref(src_pages)
    src_alloc.pages_transferred_out += len(src_pages)
    dst_alloc.pages_transferred_in += len(dst_pages)
