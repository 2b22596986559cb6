"""Batched serving engine: continuous-batching-lite over prefill/decode steps.

Slot-based continuous batching (vLLM-style, sized for the paper's
single-user edge regime up through pod-scale batches): a fixed decode batch
of B slots; every engine tick runs ONE fused decode step for all active
slots (the GEMV-batching the paper's autoregressive mode maps to on TPU).
EOS/length-complete slots free up and are refilled from the queue.

The engine is pure **mechanism**: it owns the device-side state (KV pool,
block tables, positions) and executes step functions.  All **policy** —
admission order, page budgeting, prefix reuse, eviction, preemption
victim choice — lives in ``serving.scheduler`` / ``serving.policies``
behind the ``Scheduler`` interface; the engine executes the scheduler's
``Admission`` decisions (and preemption verdicts) and reports lifecycle
events back.

Two cache disciplines, selected by the ``paged`` flag:

* **contiguous** (reference oracle): each slot owns an exact-length cache
  lane; admission prefills the whole prompt in one step (recompiling per
  prompt length) and splices the lane in.
* **paged**: K/V live in fixed pools of fixed-size pages
  (``core.kvcache``); admission allocates the slot's block table up front
  (prompt + max_new_tokens worth — all-or-nothing, so requests queue
  instead of OOMing mid-flight), prefill advances one fixed-size chunk per
  tick interleaved with decode, and completion returns the pages to the
  pool.  One compiled (chunk, decode) pair serves every prompt-length mix.
  With ``prefix_cache=True`` a radix tree maps cached prompt prefixes to
  refcounted page runs: admission starts prefill at the first uncached
  token, copying partially-shared pages copy-on-write
  (``serving.prefix_cache``).

**Architecture coverage** (paged engine): beyond attention-only decoders,

* *SSM/hybrid* archs get one recurrent-state **slab** per admitted request
  (``SlabAllocator``; slab 0 is scratch, mirroring page 0): SSM layers
  read/write their slab by slot-relative slab id while hybrid attention
  heads keep reading KV through block tables.  Slabs are zeroed at
  admission.  Preemption CHECKPOINTS the slot — recurrent state cannot be
  re-derived from donated pages — into a host-side stash (slab + resident
  KV page payloads); resume re-admits cold, restores the stash into the
  freshly allocated slab/pages, and continues exactly where it stopped
  (``stats.slab_restores``).  The token-id radix prefix cache is
  unavailable here (and raises a precise error): an SSM layer's state for
  a shared prefix is not addressable by pages.
* *Enc-dec* archs run ``encode`` once at admission: a compiled cross-KV
  write step projects the encoder memory's K/V into read-only **cross
  pages** that decode/prefill read through a second block table.  Requests
  whose frames digest matches share one encode's pages by refcount
  (``CrossKVCache``) — no copy-on-write, since cross pages are immutable
  after the write.  The token-id prefix cache is likewise unavailable
  (self-KV depends on the frames through cross-attention, so equal token
  prefixes do NOT imply equal KV — sharing would be silently wrong).

Admission budgets pages + slabs + cross pages JOINTLY (all-or-nothing),
and ``drain()`` leak-freedom extends to all three: after every admission
retires, each replica's pages are free or cache-held and its slabs free.

**Data parallelism** (``dp`` — paged engine only): the engine runs ``dp``
*replicas*, each with its own ``batch_slots`` slots and — crucially — its
own replica-local ``PageAllocator``, ``RadixPrefixCache`` and
``Scheduler`` instance, so page refcounts, prefix pins, eviction and
preemption donations never cross a replica boundary.  The page pools carry
a leading replica dim sharded over ``plan.dp_axes`` (``core.kvcache``), so
on a dp mesh each data shard stores only its replica's pages — the
paper's stationary-local-memory discipline.  A ``serving.router.Router``
assigns every submitted request to a replica (longest-prefix-hit affinity
first, then least page load) and the single ``run()`` loop drives all
replicas' slots through one compiled decode step per tick; per-replica
counters land in ``EngineStats.replicas``.  ``dp=1`` (the default) is the
old single-pool engine, token-for-token.

**Speculative decoding** (``speculative=k`` — paged, attention-only archs):
each tick a self-drafting source (``serving.prefix_cache.PromptLookupDraft``
— prompt-lookup n-grams over the slot's own context and the radix cache's
token paths; no second model) proposes up to k tokens per slot, and ONE
fused verify step (``core.steps.make_verify_step``) scores all k+1
positions, writing their KV through the block table.  Rejection sampling
(``serving.sampler.speculative_sample``) emits 1..k+1 tokens per slot,
token-identical to the one-token path: row i is sampled exactly as the
one-token path would, and drafting past row i survives only while the
sample agrees with the draft.  Rejected-draft KV needs no device-side
rollback — per-query validity masks positions past ``pos`` and the next
step overwrites position ``pos`` before any read, so the host-side
``pos``/block-table bookkeeping IS the trim.  Admission budgets +k tokens
of page headroom all-or-nothing (``Admission.spec``; denied speculation
still admits, the slot just decodes one token per tick), and a slot whose
drafts keep getting rejected stops drafting and returns the headroom pages
(``Scheduler.on_spec_trim`` — a refcount trim, safe against pages shared
with the prefix cache).

**Pipelined execution** (paged engine; ``overlap=True``, the default):
each tick splits into three phases — **plan** (host: preemption verdicts,
admissions, COW/cross/handoff planning), **collect** (the tick's single
barrier: one batched ``jax.device_get`` over every in-flight handle, then
emissions / prefill completions / deferred preemptions), and **dispatch**
(enqueue the tick's compiled steps and return without blocking).  In
overlap mode the results of tick t's dispatch are consumed at tick t+1's
collect, so the host plans tick t+1 while tick t's decode/prefill/verify
calls run on device.  Correctness needs no device-side fences: every step
threads (and donates) the cache value, so all device work serializes
through its dependency chain, and host-side planning only ever touches
pages no in-flight step references (frees happen at collect, before the
following dispatch).  ``overlap=False`` collects in the same tick — the
serial oracle.  Either way outputs are token-identical: admission/decode
timing shifts are invisible to per-request RNG streams.  ``run()``,
``drain()`` and ``preempt()`` barrier on in-flight work first, so
conservation accounting and SSM stashes never race a dispatched step.
The host phases (plan, the collect's wait, sampling, dispatch) each run
inside a named profiler span and add their seconds to an ``EngineStats``
counter (``PHASE_COUNTERS``); the compiled steps carry their own names
(``jit_paged_decode``, ``jit_prefill_chunk``, ...; ``core.steps``).

**Disaggregated serving** (``disagg=(P, D)`` with ``dp == P + D``;
attention-only archs): replicas split into P prefill-role and D
decode-role.  The router admits fresh requests only on prefill replicas,
which chunk-prefill the prompt, emit the first token, and queue the slot
for handoff; the engine then moves the finished KV page run to the
least-loaded decode replica through one compiled page-transfer step
(``core.steps.make_page_transfer_step`` — int8 scale rows ride along
byte-identically) while ``kvcache.handoff_refs`` moves refcount ownership
atomically.  Decode replicas run pure token-per-tick (or verify) steps,
so long prefills never stall another request's decode — the
prefill/decode interference that dominates TTFT tails.

Sampling is schedule-invariant: every request draws from its own seeded
RNG stream (``Request.rng``), so non-greedy outputs do not depend on
admission order, batch composition, replica routing, handoff placement,
or preemption points — and speculative decoding preserves this
per-request stream exactly.

The engine is mesh-agnostic: it drives whatever step functions
``core.steps`` built — 1-device CPU smoke or a full pod.

Machine-checked clauses (scripts/check_static.py):

Invariant: one compiled (chunk, decode, verify) step set serves every
    request mix — request lengths flow in as data, never as traced
    shapes, so the paged hot loop triggers zero recompiles after tick 1.
Enforced-by: analysis:jit-stability, analysis:traced-shape

Invariant: the per-tick path reads device values only through the single
    batched explicit jax.device_get per collect point — no hidden host
    syncs in run().
Enforced-by: analysis:host-sync

Invariant: dispatch never blocks — between dispatching a tick's compiled
    steps and the next plan phase the host performs no device barrier
    (no jax.device_get / .block_until_ready() / .item() outside collect
    points), so host planning genuinely overlaps device compute.
Enforced-by: analysis:async-barrier

Invariant: speculative headroom return is a refcount trim, never a
    free() — headroom pages may be shared with the radix prefix cache.
Enforced-by: tests/test_spec_decode.py::test_trim_releases_shared_tail_without_freeing, analysis:shared-free

Invariant: no request is lost across a membership change — ``scale_to``
    migrates (or preempt-requeues) every in-flight request of a leaving
    replica and re-places its queue on survivors, and ``kill_replica``
    re-admits the dead replica's orphans as re-prefills from host-side
    request state (prompt + emitted tokens); every submitted request
    completes, with greedy outputs token-identical to an uninterrupted
    dp=1 run and sampled outputs schedule-invariant (per-request RNG
    streams advance one draw per emitted token on every path).
Enforced-by: tests/test_elastic_serving.py::test_chaos_schedules_complete_and_match_oracle

Invariant: membership changes barrier first — ``scale_to`` and
    ``kill_replica`` consume all in-flight dispatched work (``_barrier``)
    before touching pools, allocators, or slot state, so a migration,
    reshard, or recovery never races a dispatched step's page
    references; the overlap pipeline and the serial oracle take the
    same elastic path.
Enforced-by: tests/test_elastic_serving.py::test_scale_down_mid_overlap_completes_all, analysis:async-barrier
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.kvcache import (SCRATCH_PAGE, SCRATCH_SLAB, PageAllocator,
                                SlabAllocator, cache_profile,
                                kv_pool_is_quantized, pages_needed)
from repro.serving.prefix_cache import (CrossKVCache, HostSpillStore,
                                        PromptLookupDraft, RadixPrefixCache)
from repro.serving.router import Router
from repro.serving.sampler import (SamplerConfig, sample_from_logits,
                                   speculative_sample)
from repro.serving.scheduler import (Admission, FCFSScheduler, Scheduler,
                                     effective_prompt)

# consecutive zero-accept verify steps after which a slot stops drafting
# and returns its draft-headroom pages (the speculation is clearly not
# paying for its page + compute overhead on this request)
SPEC_DISABLE_AFTER = 4


class PageTransferError(RuntimeError):
    """A cross-replica page transfer failed before it touched the cache
    (e.g. its source or destination replica died between the migration
    plan and the device copy).  The engine survives exactly this failure
    by rolling the migration back; any other exception from the transfer
    step — a trace, compile or runtime error — propagates."""


# The engine's host phases: profiler span -> the EngineStats field that
# accumulates the same interval in seconds.
PHASE_COUNTERS = {"engine.plan": "plan_s", "engine.wait": "collect_wait_s",
                  "engine.sample": "sample_s",
                  "engine.dispatch": "dispatch_s"}


class _Phase:
    """Times one host phase twice: a ``jax.profiler.TraceAnnotation`` (in
    a profiler trace's host plane, on the clock of the device ops) and its
    ``PHASE_COUNTERS`` field of ``EngineStats`` (``time.perf_counter``, so
    the counter runs with the profiler off)."""
    __slots__ = ("stats", "field", "span", "t0")

    def __init__(self, stats, span: str):
        self.stats, self.field = stats, PHASE_COUNTERS[span]
        self.span = jax.profiler.TraceAnnotation(span)

    def __enter__(self):
        self.span.__enter__()
        self.t0 = time.perf_counter()

    def __exit__(self, *exc):
        dt = time.perf_counter() - self.t0
        setattr(self.stats, self.field, getattr(self.stats, self.field) + dt)
        self.span.__exit__(*exc)


@dataclass
class Request:
    rid: int
    prompt: np.ndarray                 # (S,) int32
    frames: Optional[np.ndarray] = None  # (enc_seq_len, d_model) — enc-dec
    max_new_tokens: int = 32
    priority: int = 0                  # higher = more urgent (policies.py)
    client_id: int = 0                 # fairness accounting key (policies.py)
    seed: Optional[int] = None         # sampling stream seed (default: rid)
    rng: Optional[np.random.RandomState] = None   # set at submit
    replica: int = -1                  # routed data shard (set at submit)
    out_tokens: list = field(default_factory=list)
    done: bool = False
    # host stamps, time.perf_counter(): on Linux the same clock as
    # time.monotonic() (CLOCK_MONOTONIC), and the clock a benchmark
    # harness times its own spans with
    t_submit: float = 0.0
    t_admitted: Optional[float] = None   # first admission into a slot
    t_first_token: float = 0.0
    t_done: float = 0.0


@dataclass
class ReplicaStats:
    """Per-replica counters (``EngineStats.replicas[r]``)."""
    role: str = "mixed"                # "prefill"/"decode" under --disagg
    routed: int = 0                    # requests the router assigned here
    prefills: int = 0
    decoded_tokens: int = 0
    preemptions: int = 0
    prefix_lookups: int = 0
    prefix_hits: int = 0
    cross_lookups: int = 0             # enc-dec frames-digest lookups
    cross_hits: int = 0
    spec_denied: int = 0               # admissions denied draft headroom
    handoffs_out: int = 0              # finished page runs sent (prefill role)
    handoffs_in: int = 0               # ... received (decode role)
    pages_transferred_out: int = 0
    pages_transferred_in: int = 0

    @property
    def prefix_hit_rate(self) -> float:
        return self.prefix_hits / self.prefix_lookups \
            if self.prefix_lookups else 0.0


@dataclass
class EngineStats:
    ticks: int = 0
    prefills: int = 0
    decoded_tokens: int = 0
    prefill_tokens_skipped: int = 0    # prompt tokens served from the cache
    cow_copies: int = 0
    preemptions: int = 0
    prefix_lookups: int = 0
    prefix_hits: int = 0
    cross_lookups: int = 0             # enc-dec frames-digest lookups
    cross_hits: int = 0                # ... served from a shared encode
    cross_encodes: int = 0             # cross-KV write steps actually run
    slab_restores: int = 0             # preempted SSM state reloads
    spec_steps: int = 0                # verify slot-steps with a draft
    spec_drafted: int = 0              # draft tokens proposed to the verifier
    spec_accepted: int = 0             # draft tokens accepted
    spec_emitted: int = 0              # tokens emitted by drafted slot-steps
    spec_draft_lookups: int = 0        # draft-source queries
    spec_draft_hits: int = 0           # ... that produced a usable draft
    spec_denied: int = 0               # admissions denied draft headroom
    handoffs: int = 0                  # prefill->decode page-run transfers
    pages_transferred: int = 0         # pages moved across replicas
    transfer_failures: int = 0         # PageTransferErrors survived by rollback
    scale_events: int = 0              # scale_to membership changes applied
    crashes: int = 0                   # kill_replica recoveries
    migrations: int = 0                # in-flight slots moved off a drain
    migrated_pages: int = 0            # pages those migrations carried
    readmitted: int = 0                # requests re-placed by drain/recovery
    plan_ahead_ticks: int = 0          # plan phases run with work in flight
    plan_invalidations: int = 0        # speculative plan entries rolled back
    # host seconds per phase of the tick (``PHASE_COUNTERS``)
    plan_s: float = 0.0                # plan phase: admissions, handoffs
    collect_wait_s: float = 0.0        # host time blocked at collect points
    sample_s: float = 0.0              # host sampling of fetched logits
    dispatch_s: float = 0.0            # dispatch phase: inputs, enqueue
    tpot_s: list = field(default_factory=list)
    request_ttft: dict = field(default_factory=dict)   # rid -> seconds
    replicas: List[ReplicaStats] = field(default_factory=list)

    @property
    def ttft_s(self) -> list:
        """TTFT samples in first-token order (derived per request)."""
        return list(self.request_ttft.values())

    @property
    def accepted_tokens_per_tick(self) -> float:
        """Tokens emitted per drafted verify slot-step (> 1.0 means the
        speculation is beating the one-token path)."""
        return self.spec_emitted / self.spec_steps if self.spec_steps \
            else 0.0

    @property
    def draft_hit_rate(self) -> float:
        return self.spec_draft_hits / self.spec_draft_lookups \
            if self.spec_draft_lookups else 0.0

    @property
    def prefix_hit_rate(self) -> float:
        return self.prefix_hits / self.prefix_lookups \
            if self.prefix_lookups else 0.0

    @property
    def cross_hit_rate(self) -> float:
        return self.cross_hits / self.cross_lookups \
            if self.cross_lookups else 0.0


class ServingEngine:
    def __init__(self, cfg, plan, mesh, batch_slots: int, seq_budget: int,
                 params, prefill_fn, decode_fn, eos_id: int = 1,
                 sampler: Optional[SamplerConfig] = None, *,
                 paged: bool = False, page_size: int = 16,
                 n_pages: int = 0, prefill_chunk: int = 0,
                 prefix_cache: bool = False, scheduler=None,
                 rng_seed: int = 0, dp: int = 1, n_slabs: int = 0,
                 speculative: int = 0, verify_fn=None,
                 overlap: bool = True, disagg=None, transfer_fn=None,
                 spill=None):
        from repro.core import steps as _steps
        self.cfg, self.plan, self.mesh = cfg, plan, mesh
        assert dp >= 1, dp
        assert paged or dp == 1, "dp>1 serving requires the paged engine"
        self.R = dp                    # data-parallel replicas
        self.Bp = batch_slots          # slots per replica
        self.B = batch_slots * dp      # global slots (the decode batch)
        self.S = seq_budget
        self.params = params
        self.prefill_fn = prefill_fn   # jitted: batch=1 lane / paged chunks
        self.decode_fn = decode_fn     # jitted, batch=R*Bp
        self.eos = eos_id
        self.sampler = sampler or SamplerConfig()
        self.admissions: List[Optional[Admission]] = [None] * self.B
        self.pos = np.zeros(self.B, np.int32)
        self.last_token = np.zeros(self.B, np.int32)
        self.paged = paged
        self.stats = EngineStats(replicas=[ReplicaStats()
                                           for _ in range(self.R)])
        self.allocators: List[PageAllocator] = []
        self.prefix_caches: List[Optional[RadixPrefixCache]] = []
        self.slab_allocators: List[SlabAllocator] = []
        self.cross_caches: List[Optional[CrossKVCache]] = []
        self.router: Optional[Router] = None
        prof = cache_profile(cfg)
        self.has_ssm = paged and "ssm" in prof
        self.has_cross = paged and "cross_kv" in prof
        # int8 page pools carry per-(page, slot) scale tensors whose rows
        # must be invalidated when a page is recycled (see _plan_admissions)
        self.quant_pools = paged and kv_pool_is_quantized(plan) and \
            ("kv" in prof or "cross_kv" in prof)
        self.overlap = bool(overlap) and paged
        self.disagg = None
        self.roles: Optional[List[str]] = None
        if disagg is not None:
            p_reps, d_reps = int(disagg[0]), int(disagg[1])
            if not paged:
                raise ValueError(
                    "disaggregated serving requires the paged engine")
            if p_reps < 1 or d_reps < 1 or p_reps + d_reps != dp:
                raise ValueError(
                    f"disagg {p_reps}:{d_reps} must cover every replica "
                    f"with at least one of each role: need P >= 1, D >= 1 "
                    f"and P + D == dp ({dp})")
            if prof != {"kv"}:
                raise ValueError(
                    f"disaggregated serving is unsupported for arch "
                    f"'{cfg.name}': the page-transfer step moves self-KV "
                    f"page runs between replica pools (cache kinds "
                    f"{sorted(prof)}) — SSM slabs and cross-KV pages do "
                    f"not hand off")
            self.disagg = (p_reps, d_reps)
            self.roles = ["prefill"] * p_reps + ["decode"] * d_reps
        if paged:
            from repro.core.kvcache import paged_cache_supported
            ok, why = paged_cache_supported(cfg)
            if not ok:
                raise ValueError(
                    f"paged serving unsupported for arch '{cfg.name}': {why}")
            if prefix_cache and self.has_ssm:
                raise ValueError(
                    f"prefix_cache=True is unsupported for arch "
                    f"'{cfg.name}': its SSM layers hold recurrent state "
                    f"that a token-id prefix cannot address (cache kinds "
                    f"{sorted(prof)}); run it paged without the prefix "
                    f"cache")
            if prefix_cache and self.has_cross:
                raise ValueError(
                    f"prefix_cache=True is unsupported for arch "
                    f"'{cfg.name}': decoder self-KV depends on the "
                    f"request's encoder frames through cross-attention, "
                    f"so equal token prefixes do not imply equal KV; "
                    f"cross-KV sharing is keyed by frames digest instead "
                    f"(automatic)")
            assert seq_budget % page_size == 0, (seq_budget, page_size)
            assert prefill_chunk > 0 and seq_budget % prefill_chunk == 0, \
                (seq_budget, prefill_chunk)
            self.page_size = page_size
            self.chunk = prefill_chunk
            self.n_max_pages = seq_budget // page_size
            self.n_slabs = n_slabs or batch_slots + 1
            self.n_pool_pages = n_pages        # per-replica pool size
            self._prefix_cache_enabled = bool(prefix_cache)
            self.n_cross_pages = pages_needed(cfg.enc_seq_len, page_size) \
                if self.has_cross else 0
            # replica-local pools: refcounts never cross a replica boundary
            self.allocators = [PageAllocator(n_pages) for _ in range(dp)]
            self.prefix_caches = [
                RadixPrefixCache(a, page_size) if prefix_cache else None
                for a in self.allocators]
            self.slab_allocators = [SlabAllocator(self.n_slabs)
                                    for _ in range(dp)] if self.has_ssm \
                else []
            self.cross_caches = [CrossKVCache(a) for a in self.allocators] \
                if self.has_cross else []
            self.slot_state: List[Optional[str]] = [None] * self.B
            self.prefill_done = np.zeros(self.B, np.int32)
            self._stash: dict = {}     # rid -> preempted SSM checkpoint
            self.cache = _steps.zero_paged_cache_for(
                cfg, plan, mesh, n_pages, page_size, n_replicas=dp,
                n_slabs=self.n_slabs if self.has_ssm else 0)
        else:
            assert not prefix_cache, "prefix cache requires the paged engine"
            self.cache = _steps.zero_cache_for(cfg, plan, mesh, batch_slots,
                                               seq_budget)
        self.speculative = int(speculative)
        self.verify_fn = verify_fn
        self.draft_sources: List[PromptLookupDraft] = []
        self.spec_miss = np.zeros(self.B, np.int32)
        if self.speculative > 0:
            if not paged:
                raise ValueError(
                    "speculative decoding requires the paged engine")
            if prof != {"kv"}:
                raise ValueError(
                    f"speculative decoding is unsupported for arch "
                    f"'{cfg.name}': the k-token verify step covers "
                    f"attention-only decoders (cache kinds {sorted(prof)}) "
                    f"— SSM recurrences advance one token per step and "
                    f"enc-dec verify is not implemented")
            self.draft_sources = [PromptLookupDraft(self.prefix_caches[r])
                                  for r in range(dp)]
        if paged:
            # compiled steps come from the memoized per-shape step set
            # (steps.paged_step_set): repeated engine builds and elastic
            # membership changes reuse XLA executables instead of
            # recompiling.  Explicitly passed functions win.
            self._wire_steps(prefill_fn=prefill_fn, decode_fn=decode_fn,
                             verify_fn=verify_fn, transfer_fn=transfer_fn)
        # ``scheduler`` is either a ready instance (dp=1 only) or a factory
        # (a Scheduler subclass / functools.partial): factories receive the
        # engine-owned shared state, so callers can pass e.g.
        # ``PriorityScheduler`` without pre-building the allocator
        # themselves.  With dp>1 one instance is built per replica so every
        # policy's bookkeeping (queues, deficits, aging clocks) is
        # replica-local.
        sched = scheduler or FCFSScheduler
        if isinstance(sched, Scheduler):
            assert dp == 1, "dp>1 needs a scheduler factory, not an instance"
            assert self.disagg is None, \
                "disaggregation needs a scheduler factory, not an instance"
            self.scheds = [sched]
        else:
            self.scheds = [
                sched(seq_budget=seq_budget,
                      allocator=self.allocators[r] if paged else None,
                      page_size=page_size if paged else 0,
                      prefix_cache=self.prefix_caches[r] if paged else None,
                      slab_allocator=(self.slab_allocators[r]
                                      if self.has_ssm else None),
                      cross_cache=(self.cross_caches[r]
                                   if self.has_cross else None),
                      cross_pages_per_req=(self.n_cross_pages
                                           if self.has_cross else 0),
                      kv_pages=not paged or "kv" in prof,
                      spec_tokens=self.speculative if paged else 0,
                      stats=self.stats,
                      **({"role": self.roles[r]}
                         if self.roles is not None else {}))
                for r in range(dp)]
        for r, s in enumerate(self.scheds):
            # per-replica counters update at the scheduler's single
            # counting site, alongside the global stats
            if getattr(s, "replica_stats", None) is None:
                s.replica_stats = self.stats.replicas[r]
        if self.roles is not None:
            for r, ro in enumerate(self.roles):
                self.stats.replicas[r].role = ro
        if paged:
            self.router = Router(self.scheds, self.allocators,
                                 self.prefix_caches, page_size,
                                 cross_caches=self.cross_caches or None,
                                 roles=self.roles)
        self._rids: set = set()
        self.rng_seed = rng_seed
        # pipelined execution state: results of the previous dispatch phase
        # not yet consumed (None = nothing in flight), plus the FIFO of
        # prefill-role slots whose finished page runs await a decode home
        self._inflight: Optional[dict] = None
        self._pending_handoffs: List[int] = []
        # elastic membership: scale_to/kill_replica need the factory to
        # build schedulers for joined replicas; a hook installed here fires
        # at the top of every paged tick (fault injection, ops triggers)
        self._sched_factory = None if isinstance(sched, Scheduler) else sched
        self.membership_hook = None
        self.spill = spill
        if paged and spill is not None:
            self._restore_from_spill(spill)

    @classmethod
    def build_paged(cls, cfg, plan, mesh, batch_slots: int, seq_budget: int,
                    params, *, page_size: int = 16, n_pages: int = 0,
                    prefill_chunk: int = 16, eos_id: int = 1,
                    sampler: Optional[SamplerConfig] = None,
                    prefix_cache: bool = False, scheduler=None,
                    rng_seed: int = 0, dp: int = 1, n_slabs: int = 0,
                    speculative: int = 0, overlap: bool = True,
                    disagg=None, spill=None):
        """Construct a paged engine; its compiled (chunk, decode) pair
        (plus the cross-KV write step for enc-dec archs, the k+1-token
        verify step when ``speculative=k`` > 0, and the page-transfer step
        for dp>1 attention-only configs) comes from the memoized per-shape
        step set, so repeated builds reuse XLA executables.

        ``n_pages`` is the PER-REPLICA pool size and defaults to full
        occupancy (every slot at budget, plus every slot's cross-KV pages
        for enc-dec archs) plus the scratch page; pass something smaller to
        exercise admission control under memory pressure.  ``n_slabs``
        (SSM/hybrid archs) defaults to one recurrent-state slab per slot
        plus the scratch slab.  ``dp`` replicas each get ``batch_slots``
        slots and their own pool, driven together by one compiled step
        pair.  ``spill`` (a ``HostSpillStore``) warm-starts the prefix /
        cross caches from a previous engine's spilled page payloads."""
        from repro.core import steps as _steps
        from repro.core.kvcache import paged_cache_supported
        ok, why = paged_cache_supported(cfg)
        if not ok:
            raise ValueError(
                f"paged serving unsupported for arch '{cfg.name}': {why}")
        has_ssm, has_cross = _steps.paged_extra_inputs(cfg)
        n_max = seq_budget // page_size
        n_cross = pages_needed(cfg.enc_seq_len, page_size) if has_cross else 0
        n_pages = n_pages or batch_slots * (n_max + n_cross) + 1
        n_slabs = n_slabs or batch_slots + 1
        return cls(cfg, plan, mesh, batch_slots, seq_budget, params,
                   None, None, eos_id=eos_id,
                   sampler=sampler, paged=True, page_size=page_size,
                   n_pages=n_pages, prefill_chunk=prefill_chunk,
                   prefix_cache=prefix_cache, scheduler=scheduler,
                   rng_seed=rng_seed, dp=dp, n_slabs=n_slabs,
                   speculative=speculative, overlap=overlap, disagg=disagg,
                   spill=spill)

    # ------------------------------------------------------------------ API
    @property
    def sched(self):
        """The single scheduler (dp=1 compatibility accessor)."""
        assert self.R == 1, "dp>1: use engine.scheds[r] / has_pending()"
        return self.scheds[0]

    @property
    def allocator(self):
        """The single allocator (dp=1 compatibility accessor)."""
        if not self.paged:
            return None
        assert self.R == 1, "dp>1: use engine.allocators[r]"
        return self.allocators[0]

    @property
    def prefix_cache(self):
        """The single prefix cache (dp=1 compatibility accessor)."""
        if not self.paged:
            return None
        assert self.R == 1, "dp>1: use engine.prefix_caches[r]"
        return self.prefix_caches[0]

    @property
    def slots(self) -> List[Optional[Request]]:
        """Requests in flight, by global slot (derived from admissions)."""
        return [a.req if a is not None else None for a in self.admissions]

    def _rep(self, b: int) -> int:
        """Replica owning global slot ``b``."""
        return b // self.Bp

    def _gslot(self, r: int, local: int) -> int:
        """Replica-local slot index -> global slot index."""
        return r * self.Bp + local

    def _wire_steps(self, prefill_fn=None, decode_fn=None, verify_fn=None,
                    transfer_fn=None):
        """(Re)wire the paged engine's compiled steps from the memoized
        per-shape step set for the CURRENT replica count ``self.R`` —
        called at construction and again after every membership change.
        Explicitly passed functions win over the set's entries."""
        from repro.core import steps as _steps
        sset = _steps.paged_step_set(
            self.cfg, self.plan, self.mesh, self.Bp, self.n_pool_pages,
            self.page_size, self.n_max_pages, self.chunk,
            n_replicas=self.R,
            n_slabs=self.n_slabs if self.has_ssm else 0,
            speculative=self.speculative)
        self.prefill_fn = prefill_fn or sset["prefill"]
        self.decode_fn = decode_fn or sset["decode"]
        self.copy_fn = sset["copy"]    # COW only exists with self-KV pools
        if self.has_cross:
            self.cross_write_fn = sset["cross_write"]
        self.verify_fn = verify_fn or sset["verify"]
        self.transfer_fn = transfer_fn or sset["transfer"]

    # ------------------------------------------------- cache-tree plumbing
    def _kind_leaves(self, kind: str):
        """Leaves of one cache kind ("kv" pools / "ssm" slabs / "cross"),
        in deterministic tree order."""
        out = []
        for pat in self.cache:
            for d in pat:
                if kind in d:
                    out.extend(jax.tree_util.tree_leaves(d[kind]))
        return out

    def _update_kind(self, kind: str, fn):
        """Rebuild ``self.cache`` applying ``fn(leaf, i)`` to the i-th leaf
        of ``kind`` (same order as ``_kind_leaves``); other kinds pass
        through untouched."""
        idx = [0]

        def upd(leaf):
            res = fn(leaf, idx[0])
            idx[0] += 1
            return res

        self.cache = [[{k: (jax.tree_util.tree_map(upd, v) if k == kind
                            else v) for k, v in d.items()}
                       for d in pat] for pat in self.cache]

    def _reset_scale_rows(self, r: int, pids):
        """Zero the per-(page, slot) scale rows of recycled pages in
        replica ``r`` — scale 0 dequantizes to exact zeros, so a recycled
        page can never pair a fresh payload with a stale scale (each write
        re-sets payload + scale atomically, but rows past a new occupant's
        length would otherwise keep the previous owner's scales)."""
        idx = jnp.asarray(np.asarray(pids, np.int32))

        def upd(kind):
            self.cache = [[{k: ({kk: (vv.at[:, r, idx].set(0.0)
                                      if kk.endswith("sp") else vv)
                                 for kk, vv in v.items()}
                                if k == kind and isinstance(v, dict) else v)
                            for k, v in d.items()}
                           for d in pat] for pat in self.cache]

        upd("kv")
        upd("cross")

    def _zero_slab(self, r: int, slab: int):
        """Fresh requests start from zero recurrent state; the previous
        occupant's state persists in the pool otherwise."""
        from repro.core import steps as _steps
        self._update_kind(
            "ssm", lambda leaf, i: _steps.zero_slab(leaf, r, slab))

    def _stash_slot(self, b: int, adm, n: int):
        """Checkpoint a preempted SSM-arch slot to host: the slab (state
        after exactly ``n`` tokens) plus the payloads of the KV pages
        covering those tokens.  KV alone could be recomputed, but not
        THROUGH hybrid layers without re-advancing the SSM state — the
        resume point must restore both or neither, so both are stashed."""
        r = self._rep(b)
        stash = {"n": n, "ssm": [], "kv": [], "n_kv_pages": 0}
        for leaf in self._kind_leaves("ssm"):
            stash["ssm"].append(np.asarray(leaf[:, r, adm.slab]))
        if adm.pages:
            k = pages_needed(n, self.page_size)
            pids = jnp.asarray(np.asarray(adm.pages[:k], np.int32))
            stash["n_kv_pages"] = k
            for leaf in self._kind_leaves("kv"):
                stash["kv"].append(np.asarray(leaf[:, r, pids]))
        self._stash[adm.req.rid] = stash

    def _restore_slot(self, b: int, adm, stash):
        """Reload a stashed checkpoint into the re-admission's freshly
        allocated slab and pages; prefill then continues at token
        ``stash["n"]`` — nothing resident is recomputed."""
        r = self._rep(b)
        ssm_payload = stash["ssm"]
        self._update_kind(
            "ssm", lambda leaf, i: leaf.at[:, r, adm.slab].set(
                jnp.asarray(ssm_payload[i])))
        k = stash["n_kv_pages"]
        if k:
            pids = jnp.asarray(np.asarray(adm.pages[:k], np.int32))
            kv_payload = stash["kv"]
            self._update_kind(
                "kv", lambda leaf, i: leaf.at[:, r, pids].set(
                    jnp.asarray(kv_payload[i])))
        self.stats.slab_restores += 1

    def has_pending(self) -> bool:
        return any(s.has_pending() for s in self.scheds)

    def submit(self, req: Request):
        if req.rid in self._rids:     # rids key the per-request stats
            raise RuntimeError(f"duplicate request id {req.rid}")
        if self.cfg.is_encdec:
            want = (self.cfg.enc_seq_len, self.cfg.d_model)
            if req.frames is None:
                raise RuntimeError(
                    f"request {req.rid}: arch '{self.cfg.name}' is "
                    f"encoder-decoder — Request.frames of shape {want} "
                    f"(encoder frame embeddings) is required")
            if tuple(np.shape(req.frames)) != want:
                raise RuntimeError(
                    f"request {req.rid}: frames shape "
                    f"{tuple(np.shape(req.frames))} != {want} expected by "
                    f"arch '{self.cfg.name}' (enc_seq_len, d_model)")
        if self.disagg is not None:
            # prefill-role admission budgets the prompt only; the request
            # must still fit a decode replica's pool at handoff time
            need = pages_needed(len(req.prompt) + req.max_new_tokens,
                                self.page_size)
            usable = max(self.allocators[rr].n_pages -
                         self.allocators[rr].n_reserved
                         for rr in range(self.R)
                         if self.roles[rr] == "decode")
            if need > usable:
                raise RuntimeError(
                    f"request {req.rid} needs {need} pages to decode but "
                    f"the largest decode-replica pool has only {usable} "
                    f"usable pages — it could prefill but never hand off")
        r = self.router.route(req) if self.router is not None else 0
        self.scheds[r].submit(req)    # raises on infeasible requests
        if self.router is not None:
            self.router.commit(req, r)
        req.replica = r
        self.stats.replicas[r].routed += 1
        self._rids.add(req.rid)
        if req.rng is None:
            # one private stream per request: sampled outputs depend only on
            # (engine seed, request seed), never on scheduling or routing
            seed = req.seed if req.seed is not None else req.rid
            req.rng = np.random.RandomState([self.rng_seed, seed])
        req.t_submit = time.perf_counter()

    def run(self, max_ticks: int = 10_000):
        while (self.has_pending() or
               any(a is not None for a in self.admissions)) and \
                self.stats.ticks < max_ticks:
            self.tick()
        # final barrier: collect any work still in flight (overlap mode
        # after max_ticks exhaustion) so emitted tokens and retirements
        # land before the caller inspects state or drains
        self._barrier()
        return self.stats

    def _barrier(self):
        """Consume any in-flight dispatched work (no-op when idle); the
        engine is fully synchronous afterwards."""
        if self._inflight is not None:
            self._collect_phase()

    def drain(self) -> int:
        """Abort every in-flight admission (e.g. after ``run`` exhausted
        ``max_ticks``): each is routed through its own replica's
        ``sched.on_finish`` so its pages return to that replica's pool —
        no leaked refcounts.  Aborted requests keep ``done=False``;
        queued-but-never-admitted requests hold no resources and stay
        queued.  -> number of slots drained.

        Host-side SSM checkpoints are dropped too: a still-queued
        preempted request that resumes after a drain re-prefills from
        scratch (exact — admission plans cold and zeroes its slab)
        instead of restoring, so stash memory cannot outlive the work
        it was checkpointing."""
        self._barrier()               # in-flight work settles before abort
        n = 0
        for b in range(self.B):
            adm = self.admissions[b]
            if adm is None:
                continue
            self.scheds[self._rep(b)].on_finish(adm)
            self._clear_slot(b)
            n += 1
        if self.paged:
            self._stash.clear()
        return n

    def preempt(self, b: int):
        """Evict global slot ``b`` mid-flight.  The slot's progress needs
        no explicit snapshot: emitted tokens already live on
        ``req.out_tokens``, and resume re-admits over the *effective
        prompt* (prompt + emitted tokens), so ``pos``/``prefill_done``
        are reconstructed by ordinary admission.  The resident full pages
        are donated to the OWNING REPLICA's prefix cache via its
        ``sched.on_preempt`` — resume finds them as a prefix hit on the
        same replica (routing is sticky) and the victim's KV is reused,
        not recomputed (only the partial tail page is re-prefilled)."""
        assert self.paged, "preemption requires the paged engine"
        assert self.admissions[b] is not None, f"slot {b} is idle"
        self._barrier()               # external preempt: settle first
        if self.admissions[b] is None:
            return                    # the slot retired at that collect point
        self._preempt_now(b)

    def _preempt_now(self, b: int):
        """Immediate eviction — callers guarantee no in-flight dispatched
        step references slot ``b``'s pages (either nothing is in flight,
        or the in-flight results were just collected)."""
        adm = self.admissions[b]
        assert adm is not None, f"slot {b} is idle"
        n = int(self.prefill_done[b]) if self.slot_state[b] == "prefill" \
            else int(self.pos[b])
        if self.has_ssm:
            # recurrent state cannot be re-derived from donated pages:
            # checkpoint the slot (slab + resident KV payloads) to host
            # BEFORE the scheduler releases its resources; resume reloads
            # it (see _restore_slot)
            self._stash_slot(b, adm, n)
        resident = effective_prompt(adm.req)[:n]
        self.scheds[self._rep(b)].on_preempt(adm, resident)
        self._clear_slot(b)
        self.stats.preemptions += 1
        self.stats.replicas[self._rep(b)].preemptions += 1

    def _clear_slot(self, b: int):
        self.admissions[b] = None
        self.pos[b] = 0
        self.last_token[b] = 0
        self.spec_miss[b] = 0
        if self.paged:
            self.slot_state[b] = None
            self.prefill_done[b] = 0
            if b in self._pending_handoffs:
                self._pending_handoffs.remove(b)

    # --------------------------------------------------- elastic membership
    def scale_to(self, dp_new: int):
        """Live dp reconfiguration: grow or shrink to ``dp_new`` replicas
        without dropping an in-flight request.  Scale-down drains the
        leaving replicas first — each active slot migrates its resident KV
        pages to a survivor via the compiled page-transfer step (int8
        scale rows ride along; host refcounts hand off atomically), or
        falls back to preempt-and-requeue where migration cannot apply
        (SSM/enc-dec state, no free slot, destination pool pressure) —
        then queued requests re-route to survivors and, with a spill store
        attached, the leaving replicas' cached pages spill to host.  Both
        directions then rebuild: pools canonicalize and re-scatter to the
        new width, survivors keep their allocator/cache/scheduler objects
        (page ids and refcounts stay valid), joined replicas start fresh,
        and the compiled steps rewire from the memoized step set."""
        from repro.core import steps as _steps
        if not self.paged:
            raise ValueError("elastic membership requires the paged engine")
        if self.disagg is not None:
            raise ValueError(
                "scale_to under disaggregation is unsupported: a disagg "
                "engine's prefill/decode role sets are static")
        if self._sched_factory is None:
            raise ValueError(
                "scale_to needs a scheduler factory, not a pre-built "
                "instance (joined replicas build their own scheduler)")
        dp_new = int(dp_new)
        nd = _steps.n_dp(self.mesh, self.plan)
        if dp_new < 1 or dp_new % nd:
            raise ValueError(
                f"dp_new={dp_new} must be a positive multiple of the "
                f"mesh's data extent ({nd}) so every replica keeps a "
                f"whole device group")
        if dp_new == self.R:
            return
        self._barrier()               # in-flight work settles first
        self.stats.scale_events += 1
        if dp_new > self.R:
            self._rebuild(list(range(self.R)), dp_new)
        else:
            keep = list(range(dp_new))
            self._drain_replicas(list(range(dp_new, self.R)), keep)
            self._rebuild(keep, dp_new)
        if self.spill is not None:
            self._restore_from_spill(self.spill)

    def kill_replica(self, r: int):
        """Injected (or detected) replica FAILURE — no drain: replica
        ``r``'s device pages, allocator and scheduler state are presumed
        lost.  Recovery (runtime.ft.plan_recovery) re-admits its orphans
        on the survivors: active slots replay prompt + emitted tokens as a
        re-prefill from host-side request state (exact continuation — the
        per-request RNG stream has advanced one draw per emitted token
        either way), queued requests simply re-route.  -> the
        ``RecoveryReport``."""
        from repro.core import steps as _steps
        from repro.runtime.ft import plan_recovery
        if not self.paged:
            raise ValueError("elastic membership requires the paged engine")
        if self.disagg is not None:
            raise ValueError(
                "kill_replica under disaggregation is unsupported: a "
                "disagg engine's prefill/decode role sets are static")
        if self._sched_factory is None:
            raise ValueError(
                "kill_replica needs a scheduler factory, not a pre-built "
                "instance")
        if not 0 <= r < self.R:
            raise ValueError(f"replica {r} out of range (dp={self.R})")
        nd = _steps.n_dp(self.mesh, self.plan)
        if self.R < 2 or (self.R - 1) % nd:
            raise ValueError(
                f"cannot lose a replica at dp={self.R}: the survivor "
                f"count must stay a positive multiple of the mesh's data "
                f"extent ({nd})")
        self._barrier()
        self.stats.crashes += 1
        active = [self.admissions[b] for b in self._rep_slots(r)
                  if self.admissions[b] is not None]
        reqs, report = plan_recovery(r, active,
                                     self.scheds[r].pending_requests())
        # the dead replica's pool/allocator/caches are discarded wholesale:
        # clear its slots WITHOUT routing through on_finish (there is no
        # surviving refcount state to release into)
        for b in self._rep_slots(r):
            if self.admissions[b] is not None:
                self._clear_slot(b)
        self._rebuild([x for x in range(self.R) if x != r], self.R - 1)
        for req in reqs:
            self._place(req)
        return report

    def _drain_replicas(self, leaving: List[int], keep: List[int]):
        """Empty the leaving replicas: mark them unroutable, migrate (or
        preempt-requeue) every active slot, re-place their queues on
        survivors, and spill their cached pages to host if a spill store
        is attached.  Runs fully synchronously (callers barrier first)."""
        for r in leaving:
            self.router.mark_draining(r)
        for r in leaving:
            for b in self._rep_slots(r):
                if self.admissions[b] is None:
                    continue
                if not self._migrate_slot(b, keep):
                    # fallback: evict onto the leaving scheduler's queue
                    # (SSM slots checkpoint to host); re-placed below
                    self._preempt_now(b)
        for r in leaving:
            for req in self.scheds[r].take_queued():
                self._place(req)
        if self.spill is not None:
            # spill BEFORE the rebuild discards the leaving replicas'
            # pool rows — preempt-donated progress is captured too
            self.spill_state(self.spill, replicas=leaving)

    def _migrate_slot(self, b_src: int, keep: List[int]) -> bool:
        """Move global slot ``b_src``'s in-flight request to a surviving
        replica: claim a destination admission, copy the resident pages
        with the compiled transfer step (scale rows ride along), hand the
        refcounts off atomically, and install the slot state (pos /
        prefill progress / last token) at the destination.  -> False when
        migration cannot apply (state kinds that do not transfer, no free
        slot, destination pool pressure, or a transfer fault) — the
        destination claim, if any, is rolled back and the caller falls
        back to preemption; the source slot is left untouched."""
        if self.has_ssm or self.has_cross or self.transfer_fn is None:
            return False
        src_r = self._rep(b_src)
        adm = self.admissions[b_src]
        req = adm.req
        in_prefill = self.slot_state[b_src] == "prefill"
        n = int(self.prefill_done[b_src]) if in_prefill \
            else int(self.pos[b_src])
        cand = [r for r in keep
                if any(self.admissions[b] is None
                       for b in self._rep_slots(r))]
        if not cand:
            return False
        dst_r = self.router.decode_placement(cand)
        local = min(b - dst_r * self.Bp for b in self._rep_slots(dst_r)
                    if self.admissions[b] is None)
        dst_adm = self.scheds[dst_r].plan_migration(local, req, n)
        if dst_adm is None:
            return False              # destination pool pressure
        k = pages_needed(n, self.page_size)
        if k:
            src_pages = np.full(self.n_max_pages, SCRATCH_PAGE, np.int32)
            dst_pages = np.full(self.n_max_pages, SCRATCH_PAGE, np.int32)
            src_pages[:k] = adm.pages[:k]
            dst_pages[:k] = dst_adm.pages[:k]
            try:
                with self.mesh:
                    self.cache = self.transfer_fn(
                        self.cache, jnp.int32(src_r), jnp.int32(dst_r),
                        jnp.asarray(src_pages), jnp.asarray(dst_pages))
            except PageTransferError:
                # mid-handoff fault: no refcount moved yet (handoff_refs
                # runs only after the transfer), so retiring the claimed
                # destination admission restores the pre-migration state
                # exactly — no orphan pages on either side
                self.scheds[dst_r].on_finish(dst_adm)
                self.stats.transfer_failures += 1
                return False
        self.scheds[src_r].on_migrated(adm, k, self.allocators[dst_r],
                                       dst_adm.pages[:k])
        b_dst = self._gslot(dst_r, dst_adm.slot)
        self.admissions[b_dst] = dst_adm
        self.slot_state[b_dst] = "prefill" if in_prefill else "decode"
        self.pos[b_dst] = self.pos[b_src]
        self.prefill_done[b_dst] = self.prefill_done[b_src]
        self.last_token[b_dst] = self.last_token[b_src]
        self.spec_miss[b_dst] = self.spec_miss[b_src]
        self._clear_slot(b_src)
        req.replica = dst_r
        self.router.commit(req, dst_r)
        self.stats.migrations += 1
        self.stats.migrated_pages += k
        self.stats.pages_transferred += k
        self.stats.replicas[src_r].pages_transferred_out += k
        self.stats.replicas[dst_r].pages_transferred_in += k
        self.stats.replicas[dst_r].routed += 1
        return True

    def _place(self, req: Request):
        """Re-place an already-submitted request after a membership
        change: route (draining/dead replicas excluded), enqueue, and
        keep its identity — rid, RNG stream, submit time and emitted
        tokens all persist, so this is invisible to the client beyond
        latency.  Feasibility cannot newly fail: every replica pool has
        the same size, and the effective prompt grows exactly as
        remaining new tokens shrink."""
        r = self.router.route(req)
        self.scheds[r].submit(req)
        self.router.commit(req, r)
        req.replica = r
        self.stats.replicas[r].routed += 1
        self.stats.readmitted += 1

    def _rebuild(self, keep: List[int], dp_new: int):
        """Re-stamp the engine for ``dp_new`` replicas with survivors
        ``keep`` (old indices, order preserved): pools canonicalize and
        re-scatter (runtime.elastic.reshard_replica_pools), surviving
        replicas carry their allocator / cache / scheduler OBJECTS over
        (page ids and refcounts stay valid — position in the pool dim is
        all that changes), joined replicas start fresh, slot arrays remap,
        the router rebuilds (drain marks clear by construction; recent-
        routing windows and counters carry over), and the compiled steps
        rewire from the memoized step set."""
        from repro.runtime.elastic import reshard_replica_pools
        assert self._inflight is None and not self._pending_handoffs
        keep = list(keep)
        n_keep = len(keep)
        self.cache = reshard_replica_pools(self.cache, keep, dp_new)
        self.allocators = [self.allocators[r] for r in keep] + \
            [PageAllocator(self.n_pool_pages)
             for _ in range(dp_new - n_keep)]
        self.prefix_caches = [self.prefix_caches[r] for r in keep] + \
            [RadixPrefixCache(a, self.page_size)
             if self._prefix_cache_enabled else None
             for a in self.allocators[n_keep:]]
        if self.has_ssm:
            self.slab_allocators = \
                [self.slab_allocators[r] for r in keep] + \
                [SlabAllocator(self.n_slabs)
                 for _ in range(dp_new - n_keep)]
        if self.has_cross:
            self.cross_caches = [self.cross_caches[r] for r in keep] + \
                [CrossKVCache(a) for a in self.allocators[n_keep:]]
        self.scheds = [self.scheds[r] for r in keep]
        prof = cache_profile(self.cfg)
        for j in range(n_keep, dp_new):
            self.scheds.append(self._sched_factory(
                seq_budget=self.S,
                allocator=self.allocators[j],
                page_size=self.page_size,
                prefix_cache=self.prefix_caches[j],
                slab_allocator=(self.slab_allocators[j]
                                if self.has_ssm else None),
                cross_cache=(self.cross_caches[j]
                             if self.has_cross else None),
                cross_pages_per_req=(self.n_cross_pages
                                     if self.has_cross else 0),
                kv_pages="kv" in prof,
                spec_tokens=self.speculative,
                stats=self.stats))
        self.stats.replicas = [self.stats.replicas[r] for r in keep] + \
            [ReplicaStats() for _ in range(dp_new - n_keep)]
        for j, s in enumerate(self.scheds):
            # survivors' ReplicaStats objects moved with them; only the
            # joined replicas' schedulers need wiring
            if getattr(s, "replica_stats", None) is None:
                s.replica_stats = self.stats.replicas[j]
        # remap slot arrays: old global slot keep[j]*Bp+l -> new j*Bp+l
        old = (self.admissions, self.pos, self.last_token, self.spec_miss,
               self.slot_state, self.prefill_done)
        B_new = self.Bp * dp_new
        self.admissions = [None] * B_new
        self.pos = np.zeros(B_new, np.int32)
        self.last_token = np.zeros(B_new, np.int32)
        self.spec_miss = np.zeros(B_new, np.int32)
        self.slot_state = [None] * B_new
        self.prefill_done = np.zeros(B_new, np.int32)
        for j, r_old in enumerate(keep):
            for ll in range(self.Bp):
                ob, nb = r_old * self.Bp + ll, j * self.Bp + ll
                self.admissions[nb] = old[0][ob]
                self.pos[nb] = old[1][ob]
                self.last_token[nb] = old[2][ob]
                self.spec_miss[nb] = old[3][ob]
                self.slot_state[nb] = old[4][ob]
                self.prefill_done[nb] = old[5][ob]
                if old[0][ob] is not None:
                    old[0][ob].req.replica = j
        for j, s in enumerate(self.scheds):
            for req in s.pending_requests():
                req.replica = j
        old_router = self.router
        self.R, self.B = dp_new, B_new
        self.router = Router(self.scheds, self.allocators,
                             self.prefix_caches, self.page_size,
                             cross_caches=self.cross_caches or None)
        for j, r_old in enumerate(keep):
            self.router._recent[j].extend(old_router._recent[r_old])
            self.router._recent_frames[j].extend(
                old_router._recent_frames[r_old])
        self.router.affinity_routed = old_router.affinity_routed
        if self.speculative > 0:
            self.draft_sources = [PromptLookupDraft(self.prefix_caches[r])
                                  for r in range(dp_new)]
        self._wire_steps()

    # -------------------------------------------------------- host spill
    def spill_state(self, store=None, replicas: Optional[List[int]] = None):
        """Spill the radix-prefix and cross-KV cache contents of
        ``replicas`` (default: all) to a host-side ``HostSpillStore``:
        page payloads — int8 payloads and their per-(page, slot) scale
        rows included, byte-for-byte — keyed by token path / frames
        digest.  The pool itself is untouched (spilling takes no refs);
        restore re-allocates fresh pages wherever the store is next
        attached.  -> the store."""
        assert self.paged, "spill requires the paged engine"
        store = store if store is not None else HostSpillStore()
        for r in (replicas if replicas is not None else range(self.R)):
            pc = self.prefix_caches[r]
            if pc is not None:
                for toks, pages in pc.entries():
                    pids = jnp.asarray(np.asarray(pages, np.int32))
                    store.put_prefix(
                        toks, len(pages),
                        [np.asarray(leaf[:, r, pids])
                         for leaf in self._kind_leaves("kv")])
            if self.has_cross:
                for key, pages in self.cross_caches[r].entries():
                    pids = jnp.asarray(np.asarray(pages, np.int32))
                    store.put_cross(
                        key, len(pages),
                        [np.asarray(leaf[:, r, pids])
                         for leaf in self._kind_leaves("cross")])
        return store

    def _restore_from_spill(self, store):
        """Reload spilled cache entries into the least-loaded replicas:
        allocate fresh pages, write the stored payloads bit-for-bit
        (restored pages stay referenced, so recycled-page scale-row
        resets never touch them), and register with the replica's cache.
        Entries already resident, or not fitting the pool right now, are
        skipped — the spill store is a warm-start, not a ledger."""
        if store is None or not self.paged:
            return
        cand = [r for r in range(self.R)
                if self.prefix_caches[r] is not None]
        if cand:
            for toks, (k, payloads) in store.radix.items():
                prompt = list(toks)
                if any(self.prefix_caches[c].lookup(prompt)[0] >= len(toks)
                       for c in cand):
                    continue          # already resident somewhere
                r = min(cand, key=lambda rr: (self.router.page_load(rr), rr))
                pages = self.allocators[r].alloc(k)
                if pages is None:
                    continue          # pool pressure: stay spilled
                pids = jnp.asarray(np.asarray(pages, np.int32))
                self._update_kind(
                    "kv", lambda leaf, i, r=r, pids=pids, pl=payloads:
                    leaf.at[:, r, pids].set(jnp.asarray(pl[i])))
                self.prefix_caches[r].insert(prompt, pages)
                # the cache holds its own refs now; shared-prefix pages we
                # over-allocated drop to rc 0 here and recycle harmlessly
                self.allocators[r].decref(pages)
                store.pages_restored += k
        if self.has_cross:
            for key, (k, payloads) in store.cross.items():
                if any(xc is not None and xc.has(key)
                       for xc in self.cross_caches):
                    continue
                r = min(range(self.R),
                        key=lambda rr: (self.router.page_load(rr), rr))
                pages = self.allocators[r].alloc(k)
                if pages is None:
                    continue
                pids = jnp.asarray(np.asarray(pages, np.int32))
                self._update_kind(
                    "cross", lambda leaf, i, r=r, pids=pids, pl=payloads:
                    leaf.at[:, r, pids].set(jnp.asarray(pl[i])))
                self.cross_caches[r].insert(key, pages)
                self.allocators[r].decref(pages)
                store.pages_restored += k

    # ----------------------------------------------------------------- tick
    def tick(self):
        if self.paged:
            return self._tick_paged()
        self._admit()
        if not any(self.slots):
            return
        with self.mesh:
            logits, self.cache = self.decode_fn(
                self.params, self.cache,
                jnp.asarray(self.last_token[:, None]),
                jnp.asarray(self.pos))
        logits = np.asarray(jax.device_get(logits)).astype(np.float32)
        live = [(b, req) for b, req in enumerate(self.slots)
                if req is not None]
        with _Phase(self.stats, "engine.sample"):
            toks = [self._sample_row(logits, b, req) for b, req in live]
        now = time.perf_counter()
        for (b, req), tok in zip(live, toks, strict=True):
            self.pos[b] += 1        # the decode step wrote last_token's KV
            self._emit(b, req, tok, now)
        self.stats.ticks += 1

    def _sample_row(self, logits: np.ndarray, b: int, req: Request) -> int:
        """Sample row b from the request's own stream (schedule-invariant)."""
        return int(sample_from_logits(logits[b:b + 1], self.sampler,
                                      self.cfg.vocab_size, req.rng)[0])

    def _emit(self, b: int, req: Request, tok: int, now: float):
        """Record one generated token for slot b; retire the slot when done.

        The caller owns ``pos``: decode ticks advance it past the KV they
        just wrote before emitting; prefill completion leaves it at the
        prompt length (the sampled token's KV is written by the next decode
        tick)."""
        if not req.out_tokens:
            req.t_first_token = now
            self.stats.request_ttft[req.rid] = now - req.t_submit
        req.out_tokens.append(tok)
        self.last_token[b] = tok
        self.stats.decoded_tokens += 1
        self.stats.replicas[self._rep(b)].decoded_tokens += 1
        if tok == self.eos or len(req.out_tokens) >= req.max_new_tokens \
                or self.pos[b] >= self.S - 1:
            req.done = True
            req.t_done = now
            self.stats.tpot_s.append(
                (now - req.t_first_token) /
                max(len(req.out_tokens) - 1, 1))
            self.scheds[self._rep(b)].on_finish(self.admissions[b])
            self._clear_slot(b)

    def _admit(self):
        free = [b for b in range(self.B) if self.admissions[b] is None]
        for adm in self.scheds[0].plan(free):
            self.admissions[adm.slot] = adm
            _stamp_admitted(adm.req)
            self._prefill_into(adm.slot, adm.req)

    def _prefill_into(self, b: int, req: Request):
        """Prefill a single request and splice its cache into lane b.
        Enc-dec archs additionally run the encoder over the request's
        frames here; prefill writes the cross-KV lane the decode step
        reads."""
        from repro.core import steps as _steps
        S = len(req.prompt)
        assert S < self.S
        prompt = np.zeros((1, self.S), np.int32)
        prompt[0, :S] = req.prompt
        lane_cache = _steps.zero_cache_for(self.cfg, self.plan, self.mesh, 1,
                                           self.S)
        with self.mesh:
            if self.cfg.is_encdec:
                logits, lane_cache = self.prefill_fn(
                    self.params,
                    jnp.asarray(np.asarray(req.frames, np.float32)[None],
                                jnp.dtype(self.cfg.dtype)),
                    jnp.asarray(prompt[:, :S]), lane_cache)
            else:
                logits, lane_cache = self.prefill_fn(
                    self.params, jnp.asarray(prompt[:, :S]), lane_cache)
        self.stats.prefills += 1
        self.stats.replicas[self._rep(b)].prefills += 1
        # splice lane 0 of lane_cache into slot b of the engine cache
        self.cache = _splice_cache(self.cache, lane_cache, b)
        logits = np.asarray(jax.device_get(logits)).astype(np.float32)
        # the token sampled from the prompt's final logits IS the first
        # generated token: emit it (TTFT lands at prefill completion, and
        # max_new_tokens counts it)
        self.pos[b] = S
        with _Phase(self.stats, "engine.sample"):
            tok = self._sample_row(logits, 0, req)
        self._emit(b, req, tok, time.perf_counter())

    # ------------------------------------------------------------ paged tick
    def _tick_paged(self):
        """One pipelined tick: plan (host, overlaps in-flight device work),
        collect (the tick's single barrier — consume the PREVIOUS tick's
        dispatched results), apply deferred preemption verdicts, dispatch
        this tick's compiled steps.  ``overlap=False`` collects the fresh
        dispatch immediately — the serial oracle.  A membership hook (set
        by fault-injection harnesses or ops triggers) fires first, before
        any planning — scale_to/kill_replica barrier internally, so the
        hook sees (and leaves) a fully synchronous engine."""
        if self.membership_hook is not None:
            self.membership_hook(self)
        with _Phase(self.stats, "engine.plan"):
            tick_plan = self._plan_phase()
        self._collect_phase()
        self._run_deferred_preempts(tick_plan)
        with _Phase(self.stats, "engine.dispatch"):
            self._dispatch_phase(tick_plan)
        if not self.overlap:
            self._collect_phase()
        self.stats.ticks += 1

    def _rep_slots(self, r: int):
        return range(r * self.Bp, (r + 1) * self.Bp)

    # ------------------------------------------------------------ plan phase
    def _plan_phase(self) -> dict:
        """Host planning for this tick — runs while the previous tick's
        dispatched work is still in flight.  Preemption verdicts against
        slots with in-flight results are DEFERRED to after this tick's
        collect point (their emissions may retire the victim first —
        ``plan_invalidations``); with nothing in flight they apply
        immediately, matching the serial engine exactly.  The only device
        work enqueued here (slab zero/restore, scale-row resets) rides the
        cache value's dependency chain, so it serializes after the
        in-flight step without any host sync."""
        tick_plan = {"preempts": [], "handoffs": [], "cow": [], "cross": []}
        if self._inflight is not None:
            self.stats.plan_ahead_ticks += 1
        for r in range(self.R):
            active = [self.admissions[b] for b in self._rep_slots(r)
                      if self.admissions[b] is not None]
            for adm in self.scheds[r].plan_preemptions(
                    active, self.Bp - len(active)):
                b = self._gslot(r, adm.slot)
                if self._inflight is None:
                    self._preempt_now(b)
                else:
                    tick_plan["preempts"].append((b, adm.req.rid))
        if self.disagg is not None:
            self._plan_handoffs(tick_plan)
        self._plan_admissions(tick_plan)
        return tick_plan

    def _plan_handoffs(self, tick_plan: dict):
        """Match pending finished-prefill slots (FIFO) to decode replicas
        with a free slot.  The destination slot is claimed NOW — this
        tick's admission planning must see it occupied — but the transfer
        itself (and the source release) happens at dispatch; a deferred
        preemption landing on the source first rolls the claim back."""
        deferred = {b for b, _ in tick_plan["preempts"]}
        while self._pending_handoffs:
            b_src = self._pending_handoffs[0]
            if b_src in deferred:
                break           # source being evicted at this collect point
            src_adm = self.admissions[b_src]
            cand = [r for r in range(self.R)
                    if self.roles[r] == "decode"
                    and any(self.admissions[b] is None
                            for b in self._rep_slots(r))]
            if not cand:
                break
            dst_r = self.router.decode_placement(cand)
            local = min(b - dst_r * self.Bp for b in self._rep_slots(dst_r)
                        if self.admissions[b] is None)
            resident = int(self.pos[b_src])
            dst_adm = self.scheds[dst_r].plan_handoff(local, src_adm.req,
                                                      resident)
            if dst_adm is None:
                break           # destination pool pressure: head waits
            b_dst = self._gslot(dst_r, dst_adm.slot)
            self.admissions[b_dst] = dst_adm
            self.slot_state[b_dst] = "decode"
            self.pos[b_dst] = resident
            self.prefill_done[b_dst] = resident
            self.last_token[b_dst] = src_adm.req.out_tokens[-1]
            self.spec_miss[b_dst] = 0
            self._pending_handoffs.pop(0)
            tick_plan["handoffs"].append(
                (b_src, src_adm, dst_r, b_dst, dst_adm))

    def _plan_admissions(self, tick_plan: dict):
        """Install this tick's admissions, per replica, and assemble the
        COW / cross-KV rounds the dispatch phase will execute.  Each round
        batches one unit of work per replica (identity/scratch rows for
        replicas with nothing to do).  SSM-arch slots get their slab
        zeroed — or, for a preempted request, restored from its host-side
        stash, resuming prefill at the checkpointed token."""
        cow_rounds: List[List[Optional[Admission]]] = tick_plan["cow"]
        cross_rounds: List[List[Optional[Admission]]] = tick_plan["cross"]
        for r in range(self.R):
            free = [b - r * self.Bp for b in self._rep_slots(r)
                    if self.admissions[b] is None]
            n_cow = n_cross = 0
            for adm in self.scheds[r].plan(free):
                b = self._gslot(r, adm.slot)
                self.admissions[b] = adm
                self.slot_state[b] = "prefill"
                _stamp_admitted(adm.req)
                if adm.cow is not None:
                    if n_cow == len(cow_rounds):
                        cow_rounds.append([None] * self.R)
                    cow_rounds[n_cow][r] = adm
                    n_cow += 1
                if adm.needs_encode:
                    if n_cross == len(cross_rounds):
                        cross_rounds.append([None] * self.R)
                    cross_rounds[n_cross][r] = adm
                    n_cross += 1
                # prefix-cached tokens are already resident: prefill resumes
                # at the first uncached position (for a preempted request
                # this is its donated progress — reused, not recomputed)
                self.prefill_done[b] = adm.cached_len
                self.stats.prefill_tokens_skipped += adm.cached_len
                self.pos[b] = 0
                self.last_token[b] = 0
                if self.has_ssm:
                    stash = self._stash.pop(adm.req.rid, None)
                    if stash is not None:
                        self._restore_slot(b, adm, stash)
                        self.prefill_done[b] = stash["n"]
                        self.stats.prefill_tokens_skipped += stash["n"]
                    else:
                        self._zero_slab(r, adm.slab)
            if self.quant_pools:
                dirty = self.allocators[r].take_scale_dirty()
                if dirty:
                    self._reset_scale_rows(r, dirty)

    # --------------------------------------------------------- collect phase
    def _collect_phase(self):
        """The tick's single barrier point: one batched ``jax.device_get``
        over every in-flight handle, then host-side consumption in
        dispatch order — prefill completions (first-token emission, state
        flip to decode or the handoff queue) before decode/verify
        emissions.  Slots evicted or retired since dispatch are skipped by
        (slot, rid) guard, so a cancelled request's RNG stream is never
        advanced."""
        inf = self._inflight
        if inf is None:
            return
        self._inflight = None
        handles = [h for h, comps in inf["pf"] if comps]
        step = inf["step"]
        if step is not None:
            handles.append(step[1])
        with _Phase(self.stats, "engine.wait"):
            vals = jax.device_get(handles) if handles else []
        vi = 0
        for _, comps in inf["pf"]:
            if not comps:
                continue
            with _Phase(self.stats, "engine.sample"):
                logits_np = np.asarray(vals[vi]).astype(np.float32)
            vi += 1
            for r, b, rid, L in comps:
                adm = self.admissions[b]
                if adm is None or adm.req.rid != rid:
                    continue           # evicted since dispatch
                req = adm.req
                self.stats.prefills += 1
                self.stats.replicas[r].prefills += 1
                self.scheds[r].on_prefill_complete(adm)
                # emit the token sampled from the final prompt position —
                # the first generated token (or, on resume, the next one:
                # resumed requests re-enter with out_tokens non-empty, so
                # TTFT is not re-recorded)
                self.pos[b] = L
                with _Phase(self.stats, "engine.sample"):
                    tok = self._sample_row(logits_np, r, req)
                self._emit(b, req, tok, time.perf_counter())
                if self.admissions[b] is None:
                    continue           # retired by that token
                if self.roles is not None and self.roles[r] == "prefill":
                    # prefill-role replicas never decode: queue the slot's
                    # finished page run for transfer to a decode replica
                    self.slot_state[b] = "handoff"
                    self._pending_handoffs.append(b)
                else:
                    self.slot_state[b] = "decode"
        if step is None:
            return
        # slots still holding the request this step was dispatched for
        live = [(b, self.admissions[b].req) for b, rid in step[2]
                if self.admissions[b] is not None
                and self.admissions[b].req.rid == rid]
        if step[0] == "decode":
            # sample every row, then emit: an emission retires only its
            # own slot, so the order changes nothing
            with _Phase(self.stats, "engine.sample"):
                logits = np.asarray(vals[vi]).astype(np.float32)
                toks = [self._sample_row(logits, b, req) for b, req in live]
            now = time.perf_counter()
            for (b, req), tok in zip(live, toks, strict=True):
                self.pos[b] += 1    # the decode step wrote last_token's KV
                self._emit(b, req, tok, now)
        else:                        # verify
            drafts = step[3]
            with _Phase(self.stats, "engine.sample"):
                logits = np.asarray(vals[vi]).astype(np.float32)
            for b, req in live:
                d = drafts.get(b, [])
                with _Phase(self.stats, "engine.sample"):
                    out = speculative_sample(logits[b, :len(d) + 1], d,
                                             self.sampler,
                                             self.cfg.vocab_size, req.rng)
                now = time.perf_counter()
                emitted = 0
                for tok in out:
                    self.pos[b] += 1    # verify wrote this position's KV
                    self._emit(b, req, tok, now)
                    emitted += 1
                    if self.admissions[b] is None:
                        break           # retired mid-accept: drop the tail
                if d:
                    self.stats.spec_steps += 1
                    self.stats.spec_drafted += len(d)
                    self.stats.spec_accepted += emitted - 1
                    self.stats.spec_emitted += emitted
                    if self.admissions[b] is not None:  # retired slots reset
                        self.spec_miss[b] = 0 if emitted > 1 \
                            else self.spec_miss[b] + 1

    def _run_deferred_preempts(self, tick_plan: dict):
        """Apply preemption verdicts deferred past the collect point.  A
        victim that retired (or handed off) at collect is simply skipped —
        no release fires twice (``plan_invalidations`` counts the miss)."""
        for b, rid in tick_plan["preempts"]:
            adm = self.admissions[b]
            if adm is None or adm.req.rid != rid:
                self.stats.plan_invalidations += 1
                continue
            self._preempt_now(b)

    # -------------------------------------------------------- dispatch phase
    def _dispatch_phase(self, tick_plan: dict):
        """Enqueue this tick's compiled steps and return without blocking:
        page-run handoffs first (freshly claimed decode slots join this
        tick's decode batch), then cross-KV encodes, COW copies, prefill
        chunk rounds, and the decode-or-verify step.  Result handles land
        in ``self._inflight`` for the next collect point."""
        self._dispatch_handoffs(tick_plan)
        for round_ in tick_plan["cross"]:
            frames = np.zeros((self.R, self.cfg.enc_seq_len,
                               self.cfg.d_model), np.float32)
            cbt = np.full((self.R, self.n_cross_pages), SCRATCH_PAGE,
                          np.int32)
            for r, adm in enumerate(round_):
                if adm is not None:
                    frames[r] = np.asarray(adm.req.frames, np.float32)
                    cbt[r] = adm.cross_pages
            with self.mesh:
                self.cache = self.cross_write_fn(
                    self.params, self.cache,
                    jnp.asarray(frames, jnp.dtype(self.cfg.dtype)),
                    jnp.asarray(cbt))
            for r, adm in enumerate(round_):
                if adm is not None:
                    self.scheds[r].on_cross_written(adm)
                    self.stats.cross_encodes += 1
        for round_ in tick_plan["cow"]:
            src = np.full(self.R, SCRATCH_PAGE, np.int32)
            dst = np.full(self.R, SCRATCH_PAGE, np.int32)   # src==dst: no-op
            for r, adm in enumerate(round_):
                if adm is not None:
                    src[r], dst[r] = adm.cow
            with self.mesh:
                self.cache = self.copy_fn(self.cache,
                                          jnp.asarray(src), jnp.asarray(dst))
            for r, adm in enumerate(round_):
                if adm is not None:
                    self.scheds[r].on_cow_done(adm)
                    self.stats.cow_copies += 1
        pf = self._dispatch_prefill()
        step = self._dispatch_step()
        if pf or step is not None:
            self._inflight = {"pf": pf, "step": step}

    def _dispatch_handoffs(self, tick_plan: dict):
        """Execute the planned page-run transfers: one compiled gather →
        all-reduce → scatter step per handoff moves the source slot's
        resident pages (int8 scale rows included) into the destination
        replica's freshly allocated pages, then ``on_handoff_sent`` moves
        the refcounts atomically and the source slot clears.  A plan
        invalidated at collect (source evicted) rolls the destination
        claim back instead."""
        for b_src, src_adm, dst_r, b_dst, dst_adm in tick_plan["handoffs"]:
            if self.admissions[b_src] is not src_adm:
                self.scheds[dst_r].on_finish(dst_adm)
                self._clear_slot(b_dst)
                self.stats.plan_invalidations += 1
                continue
            req = src_adm.req
            src_r = self._rep(b_src)
            k = len(src_adm.pages)
            src_pages = np.full(self.n_max_pages, SCRATCH_PAGE, np.int32)
            dst_pages = np.full(self.n_max_pages, SCRATCH_PAGE, np.int32)
            src_pages[:k] = src_adm.pages
            dst_pages[:k] = dst_adm.pages[:k]
            with self.mesh:
                self.cache = self.transfer_fn(
                    self.cache, jnp.int32(src_r), jnp.int32(dst_r),
                    jnp.asarray(src_pages), jnp.asarray(dst_pages))
            self.scheds[src_r].on_handoff_sent(
                src_adm, self.allocators[dst_r], dst_adm.pages[:k])
            self._clear_slot(b_src)
            req.replica = dst_r
            self.stats.handoffs += 1
            self.stats.pages_transferred += k
            rs = self.stats.replicas[src_r]
            rd = self.stats.replicas[dst_r]
            rs.handoffs_out += 1
            rd.handoffs_in += 1
            rs.pages_transferred_out += k
            rd.pages_transferred_in += k

    def _bt_row(self, b: int) -> np.ndarray:
        row = np.full(self.n_max_pages, SCRATCH_PAGE, np.int32)
        adm = self.admissions[b]
        if adm is not None and adm.pages is not None:
            row[:len(adm.pages)] = adm.pages
        return row

    def _cross_row(self, b: int) -> np.ndarray:
        row = np.full(self.n_cross_pages, SCRATCH_PAGE, np.int32)
        adm = self.admissions[b]
        if adm is not None and adm.cross_pages is not None:
            row[:] = adm.cross_pages
        return row

    def _slab_id(self, b: int, active: bool = True) -> int:
        adm = self.admissions[b]
        return adm.slab if (active and adm is not None
                            and adm.slab is not None) else SCRATCH_SLAB

    def _dispatch_prefill(self):
        """Advance every prefilling slot by one chunk.  Slots are batched
        across replicas: compiled chunk call k covers each replica's k-th
        prefilling slot (replicas with fewer ride along as scratch-page
        no-ops), so the dp mesh prefills all replicas in parallel.
        -> list of (logits handle, completions) per round, consumed at the
        next collect point."""
        per_rep = [[b for b in self._rep_slots(r)
                    if self.admissions[b] is not None
                    and self.slot_state[b] == "prefill"]
                   for r in range(self.R)]
        rounds = []
        for k in range(max((len(s) for s in per_rep), default=0)):
            rows = [s[k] if k < len(s) else None for s in per_rep]
            rounds.append(self._prefill_chunk_round(rows))
        return rounds

    def _prefill_chunk_round(self, rows: List[Optional[int]]):
        """One compiled chunk call: row r advances slot ``rows[r]`` (or is
        a scratch no-op when None).  Host bookkeeping (``prefill_done``)
        advances now; -> (logits handle, [(r, b, rid, prompt_len)] for
        rows whose prompt is now fully resident) — sampling waits for the
        collect point."""
        C = self.chunk
        toks = np.zeros((self.R, C), np.int32)
        starts = np.zeros(self.R, np.int32)
        last_idx = np.zeros(self.R, np.int32)
        bt = np.full((self.R, self.n_max_pages), SCRATCH_PAGE, np.int32)
        slabs = np.full(self.R, SCRATCH_SLAB, np.int32)
        cbt = np.full((self.R, self.n_cross_pages if self.has_cross else 1),
                      SCRATCH_PAGE, np.int32)
        prompts = {}
        for r, b in enumerate(rows):
            if b is None:
                continue
            req = self.admissions[b].req
            prompt = effective_prompt(req)   # includes resumed output tokens
            prompts[r] = (b, req, prompt)
            L, c0 = len(prompt), int(self.prefill_done[b])
            n = min(C, L - c0)
            toks[r, :n] = prompt[c0:c0 + n]
            starts[r] = c0
            last_idx[r] = min(L - 1 - c0, C - 1)
            bt[r] = self._bt_row(b)
            slabs[r] = self._slab_id(b)
            if self.has_cross:
                cbt[r] = self._cross_row(b)
        args = [self.params, self.cache, jnp.asarray(toks),
                jnp.asarray(starts), jnp.asarray(last_idx), jnp.asarray(bt)]
        if self.has_ssm:
            args.append(jnp.asarray(slabs))
        if self.has_cross:
            args.append(jnp.asarray(cbt))
        with self.mesh:
            logits, self.cache = self.prefill_fn(*args)
        comps = []
        for r, (b, req, prompt) in prompts.items():
            L = len(prompt)
            self.prefill_done[b] = int(starts[r]) + C
            if int(starts[r]) + C >= L:      # prompt fully resident
                comps.append((r, b, req.rid, L))
        return logits, comps

    def _dispatch_step(self):
        """Dispatch the tick's decode-or-verify step over every
        decode-state slot.  -> ("decode", logits handle, [(b, rid)]) or
        ("verify", logits handle, [(b, rid)], drafts) or None; emissions
        happen at the next collect point."""
        active = [b for b in range(self.B)
                  if self.admissions[b] is not None
                  and self.slot_state[b] == "decode"]
        if not active:
            return None
        if self.speculative:
            drafts = self._plan_drafts(active)
            if drafts is not None:
                return self._dispatch_verify(active, drafts)
            # every draft came back empty (cold cache / no repeats):
            # fall through to the plain one-token step — identical to
            # running with speculation off
        # idle / prefilling lanes ride along pointed at the scratch page
        # (and scratch slab / scratch cross pages), so full-batch decode
        # never touches a live slab or a prefilling slot's pages
        act = set(active)
        bt = np.stack([self._bt_row(b) if b in act else
                       np.full(self.n_max_pages, SCRATCH_PAGE, np.int32)
                       for b in range(self.B)])
        pos = np.where(np.isin(np.arange(self.B), active), self.pos, 0)
        args = [self.params, self.cache,
                jnp.asarray(self.last_token[:, None]),
                jnp.asarray(pos.astype(np.int32)), jnp.asarray(bt)]
        if self.has_ssm:
            slabs = np.asarray([self._slab_id(b, b in act)
                                for b in range(self.B)], np.int32)
            args.append(jnp.asarray(slabs))
        if self.has_cross:
            cbt = np.stack([self._cross_row(b) if b in act else
                            np.full(self.n_cross_pages, SCRATCH_PAGE,
                                    np.int32) for b in range(self.B)])
            args.append(jnp.asarray(cbt))
        with self.mesh:
            logits, self.cache = self.decode_fn(*args)
        return ("decode", logits,
                [(b, self.admissions[b].req.rid) for b in active])

    # ---------------------------------------------------- speculative decode
    def _plan_drafts(self, active: List[int]):
        """Draft up to k tokens per speculation-capable active slot.
        -> {slot: draft tokens} holding only non-empty drafts, or None
        when nothing drafted (the tick falls back to the one-token step).

        A slot whose drafts were rejected ``SPEC_DISABLE_AFTER`` times in
        a row stops drafting for good and returns its headroom pages via
        ``on_spec_trim`` — a refcount trim, because those tail pages may
        meanwhile have been donated to (or matched by) the prefix cache."""
        k = self.speculative
        drafts = {}
        for b in active:
            adm = self.admissions[b]
            if not adm.spec:
                continue
            req = adm.req
            if self.spec_miss[b] >= SPEC_DISABLE_AFTER:
                keep = pages_needed(len(req.prompt) + req.max_new_tokens,
                                    self.page_size)
                self.scheds[self._rep(b)].on_spec_trim(adm, keep)
                continue
            self.stats.spec_draft_lookups += 1
            draft = self.draft_sources[self._rep(b)].draft(
                effective_prompt(req), k)
            # cap to writable coverage: verify writes KV at pos..pos+kd,
            # which must stay inside the slot's pages and the seq budget
            cov = len(adm.pages) * self.page_size
            kd = min(len(draft), cov - 1 - int(self.pos[b]),
                     self.S - 1 - int(self.pos[b]))
            if kd <= 0:
                self.spec_miss[b] += 1
                continue
            self.stats.spec_draft_hits += 1
            drafts[b] = [int(t) for t in draft[:kd]]
        return drafts or None

    def _dispatch_verify(self, active: List[int], drafts: dict):
        """One fused verify step scores k+1 positions for every active
        slot (draftless slots ride along as qlen=1 plain decode rows);
        rejection sampling at the collect point then emits 1..kd+1 tokens
        per slot.

        Rollback of rejected-draft KV is pure host bookkeeping: ``pos``
        advances only past emitted tokens, per-query validity masks
        positions >= the current ``pos``, and the next step's write lands
        on position ``pos`` before any read — so the stale KV is never
        observed and the pages stay mapped for reuse."""
        Q = self.speculative + 1
        toks = np.zeros((self.B, Q), np.int32)
        qlen = np.ones(self.B, np.int32)
        pos = np.zeros(self.B, np.int32)
        bt = np.full((self.B, self.n_max_pages), SCRATCH_PAGE, np.int32)
        for b in active:
            d = drafts.get(b, [])
            toks[b, 0] = self.last_token[b]
            toks[b, 1:1 + len(d)] = d
            qlen[b] = len(d) + 1
            pos[b] = self.pos[b]
            bt[b] = self._bt_row(b)
        with self.mesh:
            logits, self.cache = self.verify_fn(
                self.params, self.cache, jnp.asarray(toks),
                jnp.asarray(pos), jnp.asarray(qlen), jnp.asarray(bt))
        return ("verify", logits,
                [(b, self.admissions[b].req.rid) for b in active], drafts)


def _stamp_admitted(req: Request):
    """Stamp a request's first admission; a resume after preemption or a
    re-placement keeps it."""
    if req.t_admitted is None:
        req.t_admitted = time.perf_counter()


def _splice_cache(big, lane, b):
    def leaf(big_l, lane_l):
        return big_l.at[:, b:b + 1].set(lane_l[:, 0:1]) \
            if big_l.ndim >= 2 else big_l
    return jax.tree_util.tree_map(leaf, big, lane)
