"""Deterministic fault injection for elastic serving tests.

``FaultPlan`` is a seeded schedule of membership events — scale-down,
scale-up, replica crash — applied through ``engine.membership_hook``,
which fires at the top of every paged tick (where ``scale_to`` /
``kill_replica`` barrier the pipeline first), so a schedule replays
exactly from its seed regardless of overlap mode or policy.

``inject_transfer_fault`` wraps the engine's compiled page-transfer step
with a shim that raises BEFORE invoking it.  That ordering is the whole
point: the compiled step donates the cache buffer, so a fault injected
*after* entry could not leave the engine a usable cache to roll back to —
raising first models a replica dying between the migration *plan* (the
destination admission is claimed) and the device copy, the exact window
the engine's rollback arm must cover.
"""
from typing import List, Tuple

import numpy as np

from repro.serving.engine import PageTransferError


class TransferFault(PageTransferError):
    """Injected failure of a cross-replica page transfer."""


class FaultPlan:
    """Seeded membership-event schedule driven by the engine's tick clock.

    Events are ``(tick, kind, value)`` with kind ``"scale"`` (value = the
    target replica count) or ``"kill"`` (value mod the live replica count
    picks the victim).  Events that cannot apply when their tick arrives —
    scaling to the current width, killing the last replica — are skipped,
    so random schedules never need pre-validation.  ``applied`` records
    what actually fired, for assertions."""

    def __init__(self, events: List[Tuple[int, str, int]]):
        self.events = sorted(events)
        self.applied: List[Tuple[int, str, int]] = []

    @classmethod
    def random(cls, rng: np.random.RandomState, first_tick: int = 2,
               last_tick: int = 16, max_events: int = 3,
               dp_choices=(1, 2, 3)) -> "FaultPlan":
        n = int(rng.randint(1, max_events + 1))
        ticks = sorted(int(t) for t in
                       rng.randint(first_tick, last_tick + 1, n))
        events = []
        for t in ticks:
            if rng.randint(3) == 0:
                events.append((t, "kill", int(rng.randint(8))))
            else:
                events.append((t, "scale",
                               int(dp_choices[rng.randint(
                                   len(dp_choices))])))
        return cls(events)

    def install(self, engine):
        pending = list(self.events)

        def hook(e):
            while pending and e.stats.ticks >= pending[0][0]:
                tick, kind, val = pending.pop(0)
                if kind == "scale":
                    if val != e.R:
                        e.scale_to(val)
                        self.applied.append((tick, kind, val))
                elif e.R >= 2:
                    r = val % e.R
                    e.kill_replica(r)
                    self.applied.append((tick, kind, r))

        engine.membership_hook = hook
        return self


def inject_transfer_fault(engine, fail_calls=(1,)):
    """Replace ``engine.transfer_fn`` with a shim that raises
    ``TransferFault`` on the given (1-based) call numbers, BEFORE the
    compiled step runs — the donated cache buffer is never consumed, so
    the engine's rollback path sees fully intact state.  -> a state dict
    with ``calls`` / ``faults`` counters.  ``engine._wire_steps()``
    restores the real compiled step (membership changes do this
    implicitly)."""
    real = engine.transfer_fn
    fail = set(fail_calls)
    state = {"calls": 0, "faults": 0}

    def shim(*args, **kwargs):
        state["calls"] += 1
        if state["calls"] in fail:
            state["faults"] += 1
            raise TransferFault(
                f"injected fault on transfer call {state['calls']}")
        return real(*args, **kwargs)

    engine.transfer_fn = shim
    return state
