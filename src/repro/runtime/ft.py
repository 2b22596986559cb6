"""Fault tolerance: auto-resume training supervisor + heartbeat monitor.

At fleet scale the recovery path is: a node dies -> the job controller
restarts the process group -> every worker restores the latest COMMITted
checkpoint -> training resumes (data pipeline state included, so sample
order is preserved).  This module implements the single-process slice of
that contract; ``tests/test_fault_tolerance.py`` proves it by SIGKILLing a
training subprocess mid-run and verifying bit-exact continuation.

The serving twin is ``plan_recovery``: when a replica dies without a
drain, its device state (page pools, allocator refcounts) is presumed
lost, but every request's prompt and emitted tokens live host-side in the
``Request`` objects.  ``plan_recovery`` orders the dead replica's orphans
deterministically — active slots by admission sequence, then the queue in
queue order — so ``ServingEngine.kill_replica`` re-admits them elsewhere
as prefix-cache-style re-prefills and recovery is schedule-reproducible.
"""
from __future__ import annotations

import os
import subprocess
import threading
import time
from dataclasses import dataclass, field
from typing import Callable, List, Optional


@dataclass
class FTConfig:
    max_restarts: int = 5
    restart_backoff_s: float = 1.0
    heartbeat_interval_s: float = 10.0
    heartbeat_timeout_s: float = 120.0


class Heartbeat:
    """Step-progress watchdog: if no beat arrives within the timeout (a hung
    collective / dead neighbor), ``on_stall`` fires (default: hard-exit so
    the supervisor restarts from the last checkpoint — the standard
    large-scale remedy for wedged NCCL/ICI collectives)."""

    def __init__(self, timeout_s: float, on_stall: Optional[Callable] = None):
        self.timeout = timeout_s
        self.on_stall = on_stall or (lambda: os._exit(42))
        self._last = time.monotonic()
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._watch, daemon=True)

    def start(self):
        self._thread.start()
        return self

    def beat(self):
        self._last = time.monotonic()

    def stop(self):
        self._stop.set()

    def _watch(self):
        while not self._stop.wait(self.timeout / 4):
            if time.monotonic() - self._last > self.timeout:
                self.on_stall()
                return


@dataclass
class RecoveryReport:
    """What ``plan_recovery`` decided for one dead replica: which requests
    were orphaned in flight vs. still queued, in re-admission order."""
    replica: int
    active_rids: List[int] = field(default_factory=list)
    queued_rids: List[int] = field(default_factory=list)

    @property
    def n_orphans(self) -> int:
        return len(self.active_rids) + len(self.queued_rids)


def plan_recovery(replica: int, active_admissions, queued_requests):
    """-> (requests, RecoveryReport) for a replica that died mid-flight.

    ``active_admissions`` are the replica's in-flight admissions (objects
    with ``.seq`` and ``.req``); ``queued_requests`` its not-yet-admitted
    requests.  Active requests are ordered by admission sequence (oldest
    first — they have emitted the most tokens and re-prefill the most
    state, so they re-enter the queue ahead of everything newer), then the
    queue follows in its own order.  The ordering is a pure function of
    the dead replica's state, never of dict/set iteration, so crash
    recovery replays identically under a fixed fault schedule.
    """
    active = sorted(active_admissions, key=lambda adm: adm.seq)
    requests = [adm.req for adm in active] + list(queued_requests)
    report = RecoveryReport(
        replica=replica,
        active_rids=[adm.req.rid for adm in active],
        queued_rids=[req.rid for req in queued_requests])
    return requests, report


def supervise(cmd: list, cfg: Optional[FTConfig] = None,
              env: Optional[dict] = None):
    """Restart-on-failure supervisor (the per-job controller).  Returns the
    final exit code.  Exit code 0 = done; anything else restarts (with
    backoff) up to max_restarts — resumption correctness is the trainee's
    job via --auto-resume.

    The trainee runs as a child process, and a chip belongs to one
    process at a time: the calling process must not have initialized a
    JAX backend (no ``jax.devices()``, no array, no compile), or the
    child fails or hangs waiting for the chip.  Importing ``repro.runtime``
    imports ``jax`` (through ``elastic``) but touches no device; that
    stays true only while nothing in the package does at import."""
    cfg = cfg if cfg is not None else FTConfig()
    restarts = 0
    while True:
        proc = subprocess.run(cmd, env={**os.environ, **(env or {})})
        if proc.returncode == 0:
            return 0
        restarts += 1
        if restarts > cfg.max_restarts:
            return proc.returncode
        time.sleep(cfg.restart_backoff_s * restarts)
