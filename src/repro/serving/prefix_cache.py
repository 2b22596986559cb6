"""Prefix/cross caches: resident KV that admission maps instead of recomputing.

Two caches live here, both holding refcounted page runs in the replica's
``PageAllocator`` id space:

* ``RadixPrefixCache`` — token-id prefixes of *self*-attention KV
  (attention-only decoders; see the invariants below).
* ``CrossKVCache``    — encoder-memory cross-KV keyed by a digest of the
  request's frames (enc-dec archs): requests with identical frames share
  one encode's pages by refcount alone.  Cross pages are immutable after
  the admission-time write, so there is no copy-on-write and no radix
  structure — frames either match exactly or not at all.

Both caches deal purely in page *ids*, so quantized pools need nothing
extra here: a page's int8 payload and its per-(page, slot) scale rows
are indexed by the same id, and sharing, COW duplication and eviction
move/retire them together (the engine's page-copy step copies scale
rows alongside payloads; the allocator marks freed pages' scale rows
for reset before reuse).

Radix prefix cache: token-id sequences -> refcounted page runs.

The serving-layer analogue of the paper's stationary-state discipline:
KV already resident in the page pool is never recomputed or re-stored.
Completed prefills insert their prompt's full pages into a radix tree;
admission looks up the longest cached prefix and maps those pages straight
into the new slot's block table, so chunked prefill starts at the first
uncached token (system prompts, few-shot headers and agent scaffolds all
collapse onto one resident copy).

Machine-checked clauses (scripts/check_static.py; see README §Serving):

Invariant: page alignment — every node key length is a positive multiple
    of ``page_size`` and ``node.pages`` holds exactly ``len(key) /
    page_size`` page ids; children are keyed by their first page of
    tokens, so sequences that diverge mid-page live in sibling nodes.
Enforced-by: tests/test_prefix_cache.py::test_radix_split_shares_page_aligned_prefix

Invariant: cache refs — the tree holds one allocator ref per page it
    references; pages stay alive while reachable and are released only
    by eviction.
Enforced-by: tests/test_prefix_cache.py::test_allocator_refcounts, analysis:refcount-leak

Invariant: immutability — inserted pages hold KV for fully-prefilled
    prompt positions only and are never written again (the engine
    inserts only the ``len(prompt) // page_size`` full pages; the
    partial tail page stays slot-private).
Enforced-by: tests/test_prefix_cache.py::test_radix_partial_hit_mid_page_is_cow_source

Invariant: copy-on-write — a lookup matching into the middle of a node's
    first unmatched page maps the matched full pages directly and
    duplicates the partial page into a private copy
    (``steps.make_page_copy_step``) before the slot appends.
Enforced-by: tests/test_prefix_cache.py::test_scheduler_plans_cow_and_rolls_back_under_pressure

Invariant: LRU eviction — under pool pressure, leaf runs are evicted
    oldest first, and only when no live slot shares their pages
    (refcount == 1), so each eviction frees exactly ``len(node.pages)``
    pages.
Enforced-by: tests/test_prefix_cache.py::test_radix_lru_eviction_and_shared_protection

Invariant: spill restore is byte-identical — page payloads spilled to the
    ``HostSpillStore`` (including int8 payloads and their per-(page, slot)
    scale rows) restore bit-for-bit into freshly allocated pages of any
    replica, so a prefix/cross hit after a membership change reads exactly
    the bytes the original prefill/encode wrote.
Enforced-by: tests/test_elastic_serving.py::test_spill_restore_int8_byte_identity
"""
from __future__ import annotations

import hashlib
from typing import List, Optional, Tuple

import numpy as np


def _common_len(a, b) -> int:
    n = min(len(a), len(b))
    for i in range(n):
        if a[i] != b[i]:
            return i
    return n


class RadixNode:
    __slots__ = ("key", "pages", "children", "parent", "last_access")

    def __init__(self, key: tuple, pages: List[int],
                 parent: Optional["RadixNode"]):
        self.key = key
        self.pages = pages
        self.children: dict = {}
        self.parent = parent
        self.last_access = 0


class RadixPrefixCache:
    """Radix tree over token ids; holds allocator refs on cached pages."""

    def __init__(self, allocator, page_size: int):
        self.allocator = allocator
        self.psz = page_size
        self.root = RadixNode((), [], None)
        self._clock = 0          # logical LRU clock (deterministic)
        self.evictions = 0

    # ------------------------------------------------------------- queries
    @property
    def n_cached_pages(self) -> int:
        return sum(len(n.pages) for n in self._nodes())

    @property
    def n_nodes(self) -> int:
        return sum(1 for _ in self._nodes())

    @property
    def n_evictable_pages(self) -> int:
        """Pages eviction could eventually free: nodes whose whole subtree
        is unshared (refcount 1 everywhere).  A pinned descendant keeps its
        ancestors resident because only leaves are ever evicted."""
        total = 0

        def clean(node):
            nonlocal total
            ok = all(self.allocator.refcount(p) == 1 for p in node.pages)
            for ch in node.children.values():
                ok &= clean(ch)           # no short-circuit: count siblings
            if ok and node is not self.root:
                total += len(node.pages)
            return ok

        clean(self.root)
        return total

    def _nodes(self):
        stack = list(self.root.children.values())
        while stack:
            n = stack.pop()
            stack.extend(n.children.values())
            yield n

    def entries(self):
        """Yield (token_path, pages) per *leaf*, root-to-leaf accumulated.

        Leaves subsume every interior node's prefix, so spilling leaf paths
        alone captures the whole resident corpus; re-inserting them rebuilds
        the interior structure through the normal radix splits."""
        stack = [((), [], self.root)]
        while stack:
            prefix, ppages, node = stack.pop()
            path = prefix + node.key
            pages = ppages + node.pages
            if not node.children and node is not self.root:
                yield path, pages
            stack.extend((path, pages, ch) for ch in node.children.values())

    # -------------------------------------------------------------- lookup
    def lookup(self, tokens) -> Tuple[int, List[int]]:
        """Longest cached prefix of ``tokens`` -> (match_len, pages).

        ``pages`` covers ``ceil(match_len / page_size)`` pages along the
        matched path; when ``match_len`` is not page-aligned the last entry
        is the partially-matched page (copy-on-write source).  Takes no
        refs — the caller pins what it keeps before any eviction can run.
        """
        toks = [int(t) for t in tokens]
        self._clock += 1
        node, pages, matched = self.root, [], 0
        while matched < len(toks):
            rem = toks[matched:]
            child = node.children.get(tuple(rem[:self.psz]))
            if child is None:
                # no full-page match: scan for a mid-page partial match
                best, best_c = None, 0
                for ch in node.children.values():
                    c = _common_len(ch.key, rem)
                    if c > best_c:
                        best, best_c = ch, c
                if best is not None:
                    best.last_access = self._clock
                    pages.append(best.pages[0])
                    matched += best_c
                break
            c = _common_len(child.key, rem)
            child.last_access = self._clock
            n_full = c // self.psz
            pages.extend(child.pages[:n_full])
            if c % self.psz:
                pages.append(child.pages[n_full])
            matched += c
            if c < len(child.key):
                break
            node = child
        return matched, pages

    # -------------------------------------------------------------- insert
    def insert(self, tokens, pages: List[int]) -> int:
        """Cache ``pages`` (full pages of a prefilled prompt) under
        ``tokens``; len(tokens) must equal len(pages) * page_size.

        Prefix parts already cached keep their existing pages (the caller's
        duplicates stay slot-owned and die with the slot); only the new
        suffix takes cache refs.  -> number of newly referenced pages."""
        toks = [int(t) for t in tokens]
        assert len(toks) == len(pages) * self.psz, (len(toks), len(pages))
        self._clock += 1
        node, i = self.root, 0
        while i < len(toks):
            rem = toks[i:]
            child = node.children.get(tuple(rem[:self.psz]))
            if child is None:
                leaf = RadixNode(tuple(rem), list(pages[i // self.psz:]),
                                 node)
                leaf.last_access = self._clock
                self.allocator.incref(leaf.pages)
                node.children[tuple(rem[:self.psz])] = leaf
                return len(leaf.pages)
            c = _common_len(child.key, rem)
            cp = (c // self.psz) * self.psz   # split at a page boundary
            child.last_access = self._clock
            if cp < len(child.key):
                self._split(child, cp)
            i += cp
            node = child
        return 0

    def _split(self, node: RadixNode, cp: int):
        """Split ``node`` so its key ends at page-aligned offset ``cp``."""
        tail = RadixNode(node.key[cp:], node.pages[cp // self.psz:], node)
        tail.children = node.children
        tail.last_access = node.last_access
        for gc in tail.children.values():
            gc.parent = tail
        node.key = node.key[:cp]
        node.pages = node.pages[:cp // self.psz]
        node.children = {tail.key[:self.psz]: tail}

    # ------------------------------------------------------------ eviction
    def evict(self, n_pages: int) -> int:
        """Evict LRU leaf runs until >= ``n_pages`` pages return to the
        pool (or nothing evictable remains).  -> pages actually freed."""
        freed = 0
        while freed < n_pages:
            victim = None
            for n in self._nodes():
                if n.children:
                    continue                  # leaves only: children first
                if any(self.allocator.refcount(p) > 1 for p in n.pages):
                    continue                  # shared with a live slot
                if victim is None or n.last_access < victim.last_access:
                    victim = n
            if victim is None:
                break
            self.allocator.decref(victim.pages)
            freed += len(victim.pages)
            del victim.parent.children[victim.key[:self.psz]]
            self.evictions += 1
        return freed


class PromptLookupDraft:
    """Self-drafting source for speculative decoding — no second model.

    Prompt-lookup (n-gram) drafting: the longest trailing n-gram of the
    slot's context (prompt + emitted tokens) is matched against its most
    recent earlier occurrence, first within the context itself, then along
    the radix prefix cache's stored token paths; the k tokens that followed
    that occurrence become the draft.  Drafts are proposals only — the
    verify step scores them against the real model and rejection keeps
    outputs token-identical — so a bad draft costs pages, never accuracy.
    An empty return means "no guess": the engine falls back to the
    one-token decode path for that slot this tick."""

    def __init__(self, prefix_cache: Optional[RadixPrefixCache] = None,
                 max_ngram: int = 3):
        self.prefix_cache = prefix_cache
        self.max_ngram = max_ngram

    def draft(self, context, k: int) -> List[int]:
        """Propose up to ``k`` continuation tokens for ``context``."""
        if k <= 0 or len(context) < 2:
            return []
        toks = [int(t) for t in context]
        for n in range(min(self.max_ngram, len(toks) - 1), 0, -1):
            gram = toks[-n:]
            # most recent earlier occurrence within the context itself
            for i in range(len(toks) - n - 1, -1, -1):
                if toks[i:i + n] == gram:
                    out = toks[i + n:i + n + k]
                    if out:
                        return out
            # then along cached token paths (other requests' prompts)
            best: List[int] = []
            for path in self._cache_paths():
                for i in range(len(path) - n, -1, -1):
                    if list(path[i:i + n]) == gram:
                        out = [int(t) for t in path[i + n:i + n + k]]
                        if len(out) > len(best):
                            best = out
                        break
            if best:
                return best
        return []

    def _cache_paths(self):
        """Root-to-leaf token sequences of the radix cache (leaves subsume
        every interior path, so they are the whole searchable corpus)."""
        if self.prefix_cache is None:
            return
        stack = [((), self.prefix_cache.root)]
        while stack:
            prefix, node = stack.pop()
            path = prefix + node.key
            if not node.children and node is not self.prefix_cache.root:
                yield path
            stack.extend((path, ch) for ch in node.children.values())


class CrossKVCache:
    """Encoder cross-KV sharing: frames digest -> refcounted page run.

    The cache holds ONE allocator ref per page of every entry; a serving
    slot that hits takes an extra ref (``acquire``) and drops it at
    finish/preemption, so an entry's pages return to the pool only when
    the entry is evicted AND no slot still reads them.  Entries whose
    pages are unshared (refcount 1 — cache-only) are LRU-evictable under
    pool pressure.  No copy-on-write: cross pages are written once at
    admission (``steps.make_cross_kv_write_step``) and read-only after."""

    def __init__(self, allocator):
        self.allocator = allocator
        self._entries: dict = {}    # digest -> [pages, last_access]
        self._clock = 0
        self.evictions = 0

    @staticmethod
    def digest(frames) -> str:
        """Identity of an encoder input (exact-content digest)."""
        a = np.ascontiguousarray(np.asarray(frames))
        return hashlib.sha1(a.tobytes() + str(a.shape).encode()).hexdigest()

    @property
    def n_cached_pages(self) -> int:
        return sum(len(e[0]) for e in self._entries.values())

    @property
    def n_entries(self) -> int:
        return len(self._entries)

    @property
    def n_evictable_pages(self) -> int:
        return sum(len(e[0]) for e in self._entries.values()
                   if all(self.allocator.refcount(p) == 1 for p in e[0]))

    def has(self, key: str) -> bool:
        """Read-only residency probe (no refs, no LRU touch) — the dp
        router's frames-affinity signal."""
        return key in self._entries

    def acquire(self, key: str) -> Optional[List[int]]:
        """Pages for ``key`` with one extra (slot) ref taken, or None."""
        self._clock += 1
        e = self._entries.get(key)
        if e is None:
            return None
        e[1] = self._clock
        self.allocator.incref(e[0])
        return list(e[0])

    def insert(self, key: str, pages: List[int]) -> bool:
        """Adopt ``pages`` (freshly written cross-KV) under ``key``; takes
        one cache ref per page.  Returns False (no refs taken) when the
        key is already cached — the caller's pages then stay slot-private
        and die with the slot (two same-frame admissions in one tick)."""
        self._clock += 1
        if key in self._entries:
            return False
        self.allocator.incref(pages)
        self._entries[key] = [list(pages), self._clock]
        return True

    def entries(self):
        """Yield (digest, pages) for every cached encode."""
        for key, (pages, _) in self._entries.items():
            yield key, list(pages)

    def evict(self, n_pages: int) -> int:
        """Evict LRU unshared entries until >= n_pages freed (or nothing
        evictable remains).  -> pages actually freed."""
        freed = 0
        while freed < n_pages:
            victim = None
            for k, (pages, last) in self._entries.items():
                if any(self.allocator.refcount(p) > 1 for p in pages):
                    continue            # a live slot still reads them
                if victim is None or last < self._entries[victim][1]:
                    victim = k
            if victim is None:
                break
            pages, _ = self._entries.pop(victim)
            self.allocator.decref(pages)
            freed += len(pages)
            self.evictions += 1
        return freed


class HostSpillStore:
    """Host-side persistence for hot cache entries across membership changes.

    Device page pools die with their replica rows (drain shrinks the pool;
    a crash loses the rows outright), but the *payload bytes* of radix-
    prefix and cross-KV entries are pure functions of tokens/frames — so
    the engine gathers them to host numpy before a reconfiguration
    (``ServingEngine.spill_state``) and re-inserts them into survivors'
    pools afterwards (``_restore_from_spill``).  Keys are the caches' own
    identities: the leaf token path for radix entries, the frames digest
    for cross entries.  Payload lists hold one numpy array per cache leaf
    of the kind, gathered as the leaf's rows for pages ``pids`` in
    replica ``r`` (``ServingEngine._rows``) — int8 payloads and
    their scale rows are separate leaves and ride along byte-identically
    (the SSM preemption stash proved this gather/restore mechanism).

    A plain dict with overwrite semantics: re-spilling a key replaces the
    entry (latest bytes win), and the store survives engine teardown so a
    fresh engine can warm-start from it (``spill=`` ctor knob)."""

    def __init__(self):
        self.radix: dict = {}        # token path tuple -> (n_pages, payloads)
        self.cross: dict = {}        # frames digest    -> (n_pages, payloads)
        self.pages_saved = 0
        self.pages_restored = 0

    def put_prefix(self, tokens: tuple, n_pages: int, payloads):
        self.radix[tuple(int(t) for t in tokens)] = (n_pages, payloads)
        self.pages_saved += n_pages

    def put_cross(self, key: str, n_pages: int, payloads):
        self.cross[key] = (n_pages, payloads)
        self.pages_saved += n_pages

    @property
    def n_entries(self) -> int:
        return len(self.radix) + len(self.cross)
