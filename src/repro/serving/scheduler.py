"""Scheduling policy for the serving engine (policy/mechanism split).

``ServingEngine`` is pure mechanism: it owns the device-side state (page
pool, block tables, positions) and executes step functions.  Everything
discretionary — admission order, page budgeting, prefix reuse,
copy-on-write planning, cache eviction, page release — lives here, behind
the small ``Scheduler`` interface, so priority / fairness / preemptive
policies can drop in without touching the engine (``serving.policies``
ships ``PriorityScheduler`` and ``FairScheduler``).

A scheduler communicates decisions as ``Admission`` records; the engine
executes them (COW page copies, chunked prefill from the first uncached
token) and reports lifecycle events back (``on_prefill_complete``,
``on_finish``, ``on_preempt``) for the policy to update its bookkeeping.

``FCFSScheduler`` is both the stock policy and the machinery base: all
paged planning (page budgeting, prefix lookup, COW, eviction, rollback)
lives in it, and subclasses override only the queue-discipline hooks
(``_enqueue`` / ``_select_next`` / ``_put_back`` / ``_requeue_preempted``)
plus, for preemptive policies, ``plan_preemptions``.

Preemption contract: the engine calls ``plan_preemptions`` each tick and
evicts the returned victims via ``ServingEngine.preempt``, which hands the
victim's resident tokens to ``on_preempt``.  ``on_preempt`` donates the
victim's full pages to the radix prefix cache (so resume re-admits as a
prefix hit and the KV is never recomputed), releases the slot's page refs,
and re-queues the request.  A resumed request's admission plans over its
*effective prompt* — original prompt plus the tokens it already generated —
so the ordinary prefix-hit machinery restores its state.

Beyond attention-only archs, admission is a JOINT all-or-nothing budget:

* **SSM/hybrid** — one recurrent-state slab per request
  (``slab_allocator``); a request is admitted only if pages AND a slab are
  both available, and every rollback returns both.  These archs carry no
  radix prefix cache (their state is not re-derivable from token-id
  prefixes), so on preemption the ENGINE checkpoints the slot (slab +
  resident KV pages) to a host-side stash; ``on_preempt`` here just
  releases the resources and re-queues.
* **enc-dec** — ``cross_pages_per_req`` pages of encoder cross-KV from the
  same allocator: a frames-digest hit on the replica's ``CrossKVCache``
  shares the resident pages (refcount only), a miss allocates fresh pages
  and marks the admission ``needs_encode`` so the engine runs the
  cross-KV write step once; ``on_cross_written`` then publishes the pages
  for later identical-frame requests.

Disaggregated serving (``--disagg P:D``) assigns each replica's scheduler
a **role**: a ``prefill`` replica budgets only the resident-prompt page
run (the slot leaves at first token, so no decode-horizon pages are
reserved) and skips draft headroom; a ``decode`` replica plans like
``mixed`` but is fed by ``plan_handoff`` — the destination half of a page
handoff, which allocates a fresh run covering resident + remaining-decode
tokens.  ``on_handoff_sent`` then moves ownership atomically
(``kvcache.handoff_refs``).  A decode replica's own queue is populated
only by preemption requeues; it re-admits them through the ordinary
prefix-hit path over the pages the preemption donated.

Invariant: leak freedom — every page is either free, radix-cached, or
    cross-cached, and every slab is free, after ``run()``/``drain()``
    retire all admissions (asserted by tests at drain).
Enforced-by: tests/test_scheduling.py::test_drain_releases_stranded_pages, analysis:refcount-leak

Invariant: role budgeting conserves the pool — a prefill-role admission
    holds exactly the resident-prompt page run, and a handoff moves those
    references to freshly allocated destination pages exactly once, so
    per-replica leak freedom survives any interleaving of handoffs and
    preemptions.
Enforced-by: tests/test_page_transfer.py::test_handoff_preemption_mid_transfer, analysis:refcount-leak

Invariant: migration moves ownership exactly once — a drain-time
    migration (``plan_migration`` → device transfer → ``on_migrated``)
    either completes, handing the resident pages' references to the
    destination allocator and decref'ing the unfilled horizon tail at the
    source, or rolls back atomically (the destination admission is
    retired via ``on_finish`` and the source slot's estate is untouched);
    no interleaving of a crash with an in-progress handoff can orphan or
    double-free a page.
Enforced-by: tests/test_elastic_serving.py::test_crash_during_handoff_rolls_back, analysis:refcount-leak
"""
from __future__ import annotations

import collections
from dataclasses import dataclass
from typing import List, Optional, Tuple

import numpy as np

from repro.core.kvcache import handoff_refs, pages_needed


def effective_prompt(req) -> np.ndarray:
    """Tokens a (re-)admission must make resident: the original prompt plus
    everything the request already generated.  For a fresh request this is
    just the prompt; a preempted request re-prefills its own output, which
    the prefix cache turns into a hit on the pages donated at preemption
    (``on_preempt``) — reuse, not recompute."""
    out = getattr(req, "out_tokens", None)
    if not out:
        return np.asarray(req.prompt, np.int32)
    return np.concatenate([np.asarray(req.prompt, np.int32),
                           np.asarray(out, np.int32)])


def remaining_new_tokens(req) -> int:
    """Decode budget still owed to ``req`` (shrinks across preemptions, so
    effective_prompt + remaining is the constant submit-time budget)."""
    out = getattr(req, "out_tokens", None)
    return req.max_new_tokens - (len(out) if out else 0)


@dataclass
class Admission:
    """One scheduler decision: place ``req`` into engine slot ``slot``.

    pages: the slot's full block-table page run.  cached_len: prompt
    tokens already resident via prefix sharing — chunked prefill starts
    at this offset.  cow: (src, dst) page pair the
    engine must copy before the slot's first write (divergence out of a
    shared partial page).  seq: global admission order stamp (preemptive
    policies use it to pick the victim with the least sunk work).
    slab: the slot's recurrent-state slab id (SSM/hybrid archs).
    cross_pages: the slot's read-only cross-KV page run (enc-dec archs);
    needs_encode marks a frames-digest miss — the engine must run the
    cross-KV write step before this slot's first prefill chunk.
    spec: the slot's page run includes draft headroom (+spec_tokens of
    coverage past prompt + max_new_tokens), so the engine may run the
    k-token verify step on it; False means speculation was denied at
    admission (pool pressure) and the slot decodes one token per tick."""
    slot: int
    req: object
    pages: List[int]
    cached_len: int = 0
    cow: Optional[Tuple[int, int]] = None
    seq: int = 0
    slab: Optional[int] = None
    cross_pages: Optional[List[int]] = None
    needs_encode: bool = False
    spec: bool = False


class Scheduler:
    """Policy interface the engine drives.  Implementations own the wait
    queue and all allocator / prefix-cache traffic."""

    def submit(self, req) -> None:
        raise NotImplementedError

    def has_pending(self) -> bool:
        raise NotImplementedError

    def pending_requests(self) -> List:
        """Queued (not yet admitted) requests, in no particular order —
        load introspection for the dp router (``serving.router``)."""
        raise NotImplementedError

    def plan(self, free_slots: List[int]) -> List[Admission]:
        """Admissions for this tick; at most one per free slot."""
        raise NotImplementedError

    def plan_preemptions(self, active: List[Admission],
                         n_free: int) -> List[Admission]:
        """Victims to evict this tick (before admission planning).  The
        engine preempts each returned admission's slot; default: none."""
        return []

    def on_cow_done(self, adm: Admission) -> None:
        """The engine copied adm.cow — release the pin on the source."""

    def on_prefill_complete(self, adm: Admission) -> None:
        """adm's prompt is fully resident (cache-insertion hook)."""

    def on_cross_written(self, adm: Admission) -> None:
        """The engine ran adm's cross-KV write — publish the pages."""

    def on_finish(self, adm: Admission) -> None:
        """adm's request retired — release its resources."""

    def on_preempt(self, adm: Admission, resident_tokens) -> None:
        """adm was evicted mid-flight with ``resident_tokens`` computed —
        salvage its pages and re-queue the request."""
        raise NotImplementedError

    def on_spec_trim(self, adm: Admission, keep: int) -> None:
        """The engine stopped speculating on adm's slot — return the draft
        headroom pages past block-table index ``keep``; default: no-op."""

    def plan_handoff(self, slot: int, req,
                     resident_len: int) -> Optional[Admission]:
        """Destination-side admission for an incoming page handoff
        (disaggregated serving); default: refuse, the handoff stays
        queued at the source replica."""
        return None

    def on_handoff_sent(self, adm: Admission, dst_allocator,
                        dst_pages) -> None:
        """adm's resident pages were transferred to another replica —
        move reference ownership and retire the source slot."""
        raise NotImplementedError


class FCFSScheduler(Scheduler):
    """First-come-first-served admission (the seed engine's policy) with
    all-or-nothing page budgeting — the head request either gets its full
    budget (prompt + max_new_tokens) or the whole queue waits
    (no mid-flight OOM, no starvation-by-overtaking) — plus optional radix
    prefix sharing: admission maps the longest cached prefix into the block
    table, duplicating a partially-shared page copy-on-write, and evicts
    LRU cache runs when the pool can't cover the remainder."""

    def __init__(self, *, seq_budget: int, allocator, page_size: int,
                 prefix_cache=None, stats=None, slab_allocator=None,
                 cross_cache=None, cross_pages_per_req: int = 0,
                 kv_pages: bool = True, spec_tokens: int = 0,
                 role: str = "mixed"):
        assert role in ("mixed", "prefill", "decode"), role
        self.queue: collections.deque = collections.deque()
        self.seq_budget = seq_budget
        # disaggregation role: "prefill" budgets only the resident-prompt
        # page run (the slot hands off at first token) and never reserves
        # draft headroom; "decode" plans like "mixed" (the marker is for
        # the router's placement)
        self.role = role
        self.allocator = allocator
        self.psz = page_size
        self.prefix_cache = prefix_cache
        # False for pure-SSM archs: no layer has a KV pool, so per-token
        # page demand is zero (state lives entirely in the slab)
        self.kv_pages = kv_pages
        self.slab_allocator = slab_allocator        # SSM/hybrid archs
        self.cross_cache = cross_cache              # enc-dec archs
        self.cross_pages_per_req = cross_pages_per_req
        # speculative-decoding draft headroom: admissions try to budget
        # +spec_tokens of extra page coverage so the verify step can write
        # drafted positions past prompt + max_new_tokens (0 = off)
        self.spec_tokens = spec_tokens
        # cross pages planned this tick but not yet written: a second
        # same-frame admission in the same plan() round shares them
        # instead of running a duplicate encode
        self._pending_cross: dict = {}
        self.stats = stats
        self._round = 0      # logical clock: one tick per plan() call
        self._adm_seq = 0    # admission order stamp
        # page demand of the queued backlog, maintained at every
        # enqueue/dequeue (submit / admission / put-back / requeue) so the
        # dp router's load probe is O(1) instead of a queue scan
        self.backlog_pages = 0
        # per-replica counter block (engine-assigned for dp engines) —
        # written at the SAME site as the global stats so the two hit
        # rates cannot drift
        self.replica_stats = None

    # ------------------------------------------------- queue discipline hooks
    def _enqueue(self, req) -> None:
        self.queue.append(req)

    def _select_next(self):
        """Next request to try admitting, or None."""
        return self.queue.popleft() if self.queue else None

    def _put_back(self, req) -> None:
        """Selected request could not be admitted (page pressure): it stays
        head-of-line so nothing overtakes it."""
        self.queue.appendleft(req)

    def _requeue_preempted(self, req) -> None:
        """Preempted request returns to the queue; FCFS resumes it first."""
        self.queue.appendleft(req)

    # ------------------------------------------------------------- intake
    def submit(self, req) -> None:
        if len(req.prompt) == 0:
            raise RuntimeError(f"request {req.rid} has an empty prompt")
        if len(req.prompt) + req.max_new_tokens > self.seq_budget:
            raise RuntimeError(
                f"request {req.rid} needs {len(req.prompt)} prompt + "
                f"{req.max_new_tokens} new tokens; the sequence budget "
                f"is {self.seq_budget}")
        need = self._req_pages(req)
        usable = self.allocator.n_pages - self.allocator.n_reserved
        if need > usable:           # reject now, not mid-run at admission
            raise RuntimeError(
                f"request {req.rid} needs {need} pages"
                + (f" (incl. {self.cross_pages_per_req} cross-KV)"
                   if self.cross_pages_per_req else "")
                + f"; the pool only has {usable} usable")
        self._enqueue(req)
        self.backlog_pages += self._req_pages(req)

    def has_pending(self) -> bool:
        return bool(self.queue)

    def pending_requests(self) -> List:
        return list(self.queue)

    def _req_pages(self, req) -> int:
        """Page demand of one queued request (cross-KV included).
        Constant while it waits (out_tokens only grow while admitted), so
        the backlog counter's add/subtract stay symmetric across put-backs
        and requeues."""
        n = len(effective_prompt(req))
        if self.role != "prefill":      # prefill slots leave at first token
            n += remaining_new_tokens(req)
        return (pages_needed(n, self.psz) if self.kv_pages else 0) \
            + self.cross_pages_per_req

    def _evictable_pages(self) -> int:
        """Pages eviction could eventually reclaim across both caches."""
        n = 0
        if self.prefix_cache is not None:
            n += self.prefix_cache.n_evictable_pages
        if self.cross_cache is not None:
            n += self.cross_cache.n_evictable_pages
        return n

    def _reclaim(self, shortfall: int) -> None:
        """Evict cached runs until ``shortfall`` pages are freed (radix
        prefix leaves first — they are rebuildable per request — then
        whole cross-KV entries)."""
        if self.prefix_cache is not None:
            shortfall -= self.prefix_cache.evict(shortfall)
        if shortfall > 0 and self.cross_cache is not None:
            self.cross_cache.evict(shortfall)

    def _admissible_without_eviction(self, req) -> bool:
        """True if a free slot could actually serve ``req`` right now —
        pool pages and state slabs included.  A free slot whose pool is
        exhausted must not suppress preemption: evicting a victim is what
        frees the pages (and its slab)."""
        if self.slab_allocator is not None and \
                self.slab_allocator.n_free == 0:
            return False
        return self.allocator.n_free + self._evictable_pages() \
            >= self._req_pages(req)

    # ---------------------------------------------------------- admission
    def plan(self, free_slots: List[int]) -> List[Admission]:
        self._round += 1
        out = []
        for slot in free_slots:
            req = self._select_next()
            if req is None:
                break
            self.backlog_pages -= self._req_pages(req)
            adm = self._plan_paged(slot, req)
            if adm is None:         # blocked: wait for reclamation
                self._put_back(req)
                self.backlog_pages += self._req_pages(req)
                break
            adm.seq = self._adm_seq
            self._adm_seq += 1
            out.append(adm)
        return out

    def _can_reclaim(self, need: int) -> bool:
        """True if evicting cache runs can actually cover a ``need``-page
        allocation (free pages + eventually-evictable cached pages, radix
        and cross-KV caches both)."""
        ev = self._evictable_pages()
        return ev > 0 and self.allocator.n_free + ev >= need

    def _plan_paged(self, slot: int, req) -> Optional[Admission]:
        prompt = effective_prompt(req)
        L = len(prompt)
        horizon = L if self.role == "prefill" \
            else L + remaining_new_tokens(req)
        total = pages_needed(horizon, self.psz) if self.kv_pages else 0
        alloc = self.allocator
        # ---- recurrent-state slab (SSM/hybrid): all-or-nothing with pages
        slab = None
        if self.slab_allocator is not None:
            slab = self.slab_allocator.alloc()
            if slab is None:        # every slot busy or leaked — wait
                return None
        cached_len, run = 0, []
        if self.prefix_cache is not None:
            matched, run = self.prefix_cache.lookup(prompt)
            # always prefill >= 1 token: the final prompt position's logits
            # seed the first decode
            cached_len = min(matched, max(L - 1, 0))
        n_full = cached_len // self.psz
        shared = run[:n_full]
        cow_src = run[n_full] if cached_len % self.psz else None
        # pin the reused pages before eviction (below) can touch them
        alloc.incref(shared)
        if cow_src is not None:
            alloc.incref([cow_src])
        need = total - n_full
        fresh = alloc.alloc(need)
        if fresh is None and self._can_reclaim(need):
            # evict only when it actually covers the shortfall — a futile
            # eviction would wipe hot prefixes and still leave us blocked
            self._reclaim(need - alloc.n_free)
            fresh = alloc.alloc(need)
        if fresh is None and (shared or cow_src is not None):
            # Prefix reuse itself can block admission: the pins above make
            # the matched run unevictable, and the leftover fresh-page need
            # may exceed what eviction can reclaim — forever, if no other
            # slot is in flight.  Degrade to a cold prefill: drop the pins
            # (the run becomes evictable), reclaim, take the budget fresh.
            alloc.decref(shared)
            if cow_src is not None:
                alloc.decref([cow_src])
            shared, cow_src, cached_len, n_full = [], None, 0, 0
            need = total
            if alloc.n_free < need and self._can_reclaim(need):
                self._reclaim(need - alloc.n_free)
            fresh = alloc.alloc(need)
        if fresh is None:           # roll the pins back; the head blocks
            alloc.decref(shared)
            if cow_src is not None:
                alloc.decref([cow_src])
            if slab is not None:
                self.slab_allocator.free(slab)
            return None
        # ---- encoder cross-KV (enc-dec): digest hit shares, miss encodes
        cross_pages, needs_encode = None, False
        if self.cross_cache is not None:
            key = self.cross_cache.digest(req.frames)
            cross_pages = self.cross_cache.acquire(key)
            if cross_pages is None and key in self._pending_cross:
                # same frames admitted earlier this tick: its write step
                # runs before any read, so sharing is already safe
                cross_pages = list(self._pending_cross[key])
                alloc.incref(cross_pages)
            if cross_pages is None:
                needs_encode = True
                ncross = self.cross_pages_per_req
                cross_pages = alloc.alloc(ncross)
                if cross_pages is None and self._can_reclaim(ncross):
                    self._reclaim(ncross - alloc.n_free)
                    cross_pages = alloc.alloc(ncross)
                if cross_pages is None:   # joint rollback; the head blocks
                    alloc.decref(shared + fresh)
                    if cow_src is not None:
                        alloc.decref([cow_src])
                    if slab is not None:
                        self.slab_allocator.free(slab)
                    return None
                self._pending_cross[key] = list(cross_pages)
        # ---- speculative draft headroom: +spec_tokens of page coverage so
        # the verify step can write drafted positions past the base budget.
        # Opportunistic and all-or-nothing: on pool pressure the request is
        # still admitted, just without speculation (adm.spec=False), and no
        # cache eviction runs — hot resident prefixes outrank draft room.
        spec, spec_pages = False, []
        if self.spec_tokens > 0 and self.kv_pages \
                and self.role != "prefill":
            n_max = self.seq_budget // self.psz
            extra = min(pages_needed(L + remaining_new_tokens(req) +
                                     self.spec_tokens, self.psz),
                        n_max) - total
            spec_pages = alloc.alloc(extra)
            if spec_pages is None:
                spec_pages = []
                for st in (self.stats, self.replica_stats):
                    if st is not None:
                        st.spec_denied += 1
            else:
                spec = True
        # count stats on admission only — a blocked head-of-line request is
        # re-planned every tick and must not inflate the hit rates
        if self.prefix_cache is not None:
            for st in (self.stats, self.replica_stats):
                if st is not None:
                    st.prefix_lookups += 1
                    st.prefix_hits += cached_len > 0
        if self.cross_cache is not None:
            for st in (self.stats, self.replica_stats):
                if st is not None:
                    st.cross_lookups += 1
                    st.cross_hits += not needs_encode
        # fresh[0] sits at block-table index n_full: exactly where the COW
        # copy of the partial page belongs
        cow = (cow_src, fresh[0]) if cow_src is not None else None
        return Admission(slot=slot, req=req,
                         pages=shared + fresh + spec_pages,
                         cached_len=cached_len, cow=cow, slab=slab,
                         cross_pages=cross_pages, needs_encode=needs_encode,
                         spec=spec)

    # ------------------------------------------------------------- events
    def on_cow_done(self, adm: Admission) -> None:
        self.allocator.decref([adm.cow[0]])

    def on_prefill_complete(self, adm: Admission) -> None:
        if self.prefix_cache is None:
            return
        prompt = effective_prompt(adm.req)
        n_full = len(prompt) // self.psz    # the partial tail stays private
        if n_full:
            self.prefix_cache.insert(prompt[:n_full * self.psz],
                                     adm.pages[:n_full])

    def on_cross_written(self, adm: Admission) -> None:
        """The engine encoded adm's frames and wrote its cross pages —
        publish them for later identical-frame requests (the cache takes
        its own refs) and retire the same-tick pending entry."""
        key = self.cross_cache.digest(adm.req.frames)
        self._pending_cross.pop(key, None)
        self.cross_cache.insert(key, adm.cross_pages)

    def _release(self, adm: Admission) -> None:
        """Drop every resource an admission holds (pages, slab, cross)."""
        self.allocator.decref(adm.pages)
        if adm.slab is not None:
            self.slab_allocator.free(adm.slab)
        if adm.cross_pages is not None:
            self.allocator.decref(adm.cross_pages)

    def on_finish(self, adm: Admission) -> None:
        self._release(adm)

    def on_spec_trim(self, adm: Admission, keep: int) -> None:
        """The engine stopped speculating on adm's slot (persistent draft
        misses) — return the headroom pages past block-table index ``keep``
        to the pool.  Tail pages of a partially rejected draft may be
        shared with the radix prefix cache by the time the trim runs (a
        preemption donated them, or an identical prompt was inserted), so
        this drops a *reference* per page (``allocator.trim``) rather than
        assert-freeing."""
        self.allocator.trim(adm.pages[keep:])
        del adm.pages[keep:]
        adm.spec = False

    def on_preempt(self, adm: Admission, resident_tokens) -> None:
        """Salvage an evicted slot: donate its resident *full* pages to the
        prefix cache (resume finds them as a prefix hit — the victim's KV
        is reused, never recomputed), drop the slot's page refs (slab and
        cross-KV refs too — SSM state travels via the engine's host-side
        stash instead, and cross pages usually stay resident in the
        cross-KV cache), and re-queue the request.  The partial tail page
        is slot-private KV and is simply freed; resume re-prefills those
        few tokens."""
        if self.prefix_cache is not None:
            n_full = len(resident_tokens) // self.psz
            if n_full:
                self.prefix_cache.insert(
                    resident_tokens[:n_full * self.psz], adm.pages[:n_full])
        self._release(adm)
        self._requeue_preempted(adm.req)
        self.backlog_pages += self._req_pages(adm.req)

    # ------------------------------------------------------ disaggregation
    def plan_handoff(self, slot: int, req,
                     resident_len: int) -> Optional[Admission]:
        """Destination-side admission for an incoming page handoff: the
        request arrives with ``resident_len`` tokens of KV already computed
        on the source replica, so this allocates a fresh run covering
        resident + remaining-decode tokens (the device transfer step fills
        the resident prefix; reference ownership moves separately via
        ``on_handoff_sent`` → ``kvcache.handoff_refs``).  All-or-nothing
        like ``_plan_paged``: returning None leaves the handoff queued at
        the source.  Draft headroom is budgeted opportunistically, exactly
        as at a cold admission."""
        total = pages_needed(resident_len + remaining_new_tokens(req),
                             self.psz)
        alloc = self.allocator
        fresh = alloc.alloc(total)
        if fresh is None and self._can_reclaim(total):
            self._reclaim(total - alloc.n_free)
            fresh = alloc.alloc(total)
        if fresh is None:
            return None
        spec, spec_pages = False, []
        if self.spec_tokens > 0:
            n_max = self.seq_budget // self.psz
            extra = min(pages_needed(resident_len +
                                     remaining_new_tokens(req) +
                                     self.spec_tokens, self.psz),
                        n_max) - total
            spec_pages = alloc.alloc(extra)
            if spec_pages is None:
                spec_pages = []
                for st in (self.stats, self.replica_stats):
                    if st is not None:
                        st.spec_denied += 1
            else:
                spec = True
        adm = Admission(slot=slot, req=req, pages=fresh + spec_pages,
                        cached_len=resident_len, spec=spec)
        adm.seq = self._adm_seq
        self._adm_seq += 1
        return adm

    def on_handoff_sent(self, adm: Admission, dst_allocator,
                        dst_pages) -> None:
        """The engine transferred adm's resident pages to another replica:
        move reference ownership atomically (the source refs drop exactly
        once; pages the radix cache shares stay resident here).  Prefill
        admissions hold no slab or cross pages — disaggregation is gated
        to attention-only archs — so the page refs are the whole estate."""
        handoff_refs(self.allocator, adm.pages, dst_allocator, dst_pages)

    # ------------------------------------------------- elastic membership
    def plan_migration(self, slot: int, req,
                       resident_len: int) -> Optional[Admission]:
        """Destination-side admission for a drain-time slot migration.

        Unlike ``plan_handoff`` (whose source is a finished prefill, so
        resident + remaining covers everything), a migrating slot may
        still be mid-prefill — so this budgets the full cold-admission
        horizon, ``len(effective_prompt) + remaining_new_tokens`` (the
        constant submit-time budget).  That total always covers the
        resident pages: a slot's resident length never exceeds its
        effective prompt + emitted tokens.  All-or-nothing: returning
        None makes the engine fall back to preempt-and-requeue."""
        total = pages_needed(len(effective_prompt(req)) +
                             remaining_new_tokens(req), self.psz)
        alloc = self.allocator
        fresh = alloc.alloc(total)
        if fresh is None and self._can_reclaim(total):
            self._reclaim(total - alloc.n_free)
            fresh = alloc.alloc(total)
        if fresh is None:
            return None
        spec, spec_pages = False, []
        if self.spec_tokens > 0:
            n_max = self.seq_budget // self.psz
            extra = min(pages_needed(len(effective_prompt(req)) +
                                     remaining_new_tokens(req) +
                                     self.spec_tokens, self.psz),
                        n_max) - total
            spec_pages = alloc.alloc(extra)
            if spec_pages is None:
                spec_pages = []
                for st in (self.stats, self.replica_stats):
                    if st is not None:
                        st.spec_denied += 1
            else:
                spec = True
        adm = Admission(slot=slot, req=req, pages=fresh + spec_pages,
                        cached_len=resident_len, spec=spec)
        adm.seq = self._adm_seq
        self._adm_seq += 1
        return adm

    def on_migrated(self, adm: Admission, k: int, dst_allocator,
                    dst_pages) -> None:
        """The engine transferred adm's first ``k`` (resident) pages to
        another replica: hand exactly those references over atomically,
        then drop the unfilled horizon tail.  The tail goes through
        ``decref`` rather than ``free`` — a preemption elsewhere may have
        donated overlapping pages to the radix cache by now."""
        if k:
            handoff_refs(self.allocator, adm.pages[:k],
                         dst_allocator, dst_pages)
        self.allocator.decref(adm.pages[k:])
        if adm.cross_pages is not None:
            self.allocator.decref(adm.cross_pages)

    def take_queued(self) -> List:
        """Drain-time queue takeover: every queued (not yet admitted)
        request leaves this scheduler for re-placement elsewhere; the
        backlog counter returns to zero with them."""
        out = self.pending_requests()
        for req in out:
            self.backlog_pages -= self._req_pages(req)
        self._clear_queue()
        return out

    def _clear_queue(self) -> None:
        self.queue.clear()
