"""Reduce a profiler trace by the program's own names: device time per
compiled step, and idle device time per host phase.

The serving program names each compiled step (the device plane's ``XLA
Modules`` line holds ``jit_paged_decode(<fingerprint>)``,
``jit_prefill_chunk(...)``, ...) and wraps each host phase of a tick in a
span (``engine.plan``, ``engine.wait``, ``engine.sample``,
``engine.dispatch``).  The harness adds ``tick``, ``submit`` and
``generator-wait`` and marks the traced slice with ``bench.trace.WINDOW``.
From the slice:

* ``modules``: per step name, the device duration of each of its module
  events that lie wholly inside the slice, in seconds.  The device's
  record stops with the profiler and cuts the step still running then;
  an event that ends where the device's record ends is left out;
* ``idle_by_phase``: the slice's idle device seconds (no ``XLA Ops``
  event running), each instant put down to the innermost host span open
  then: a phase span by its name, ``tick`` alone as ``tick-other``, no
  span as ``idle``; averaged over the devices that ran anything;
* ``idle_gaps_by_phase``: the ten longest idle gaps, each named by the
  phase that holds most of it.

A trace of a program without these names yields no step and puts idle
time under ``tick-other`` and ``idle`` alone; ``step_ms`` then reads None.
"""
from __future__ import annotations

import bisect
import statistics

from bench import trace

MODULES_LINE = "XLA Modules"
PHASE_SPANS = ("engine.plan", "engine.wait", "engine.sample",
               "engine.dispatch", "submit", "generator-wait")
TICK, TICK_OTHER, IDLE = "tick", "tick-other", "idle"


def module_name(text: str) -> str:
    """``jit_paged_decode(1234)`` -> ``jit_paged_decode``."""
    return text.split("(", 1)[0]


def _host_spans(pd):
    """-> (slice (lo, hi), [(start, end, name)] of the phase and tick
    spans), in ns."""
    win, spans = None, []
    for p in pd.planes:
        if not p.name.startswith("/host:"):
            continue
        for line in p.lines:
            for ev in line.events:
                if ev.name == trace.WINDOW:
                    win = (ev.start_ns, ev.end_ns)
                elif ev.name in PHASE_SPANS or ev.name == TICK:
                    spans.append((ev.start_ns, ev.end_ns, ev.name))
    if win is None:
        raise ValueError(f"the trace holds no {trace.WINDOW!r} span")
    return win, spans


def _segments(lo, hi, spans):
    """-> (cuts, names): the slice cut at every span edge, and the phase
    of each piece ``[cuts[i], cuts[i + 1])``, which is the open span that
    started last (the innermost, as spans nest)."""
    cuts = sorted({lo, hi} | {x for s in spans for x in s[:2] if lo < x < hi})
    names = []
    for x, y in zip(cuts, cuts[1:]):
        open_ = [s for s in spans if s[0] <= x and s[1] >= y]
        if not open_:
            names.append(IDLE)
            continue
        name = max(open_, key=lambda s: (s[0], -s[1]))[2]
        names.append(TICK_OTHER if name == TICK else name)
    return cuts, names


def _phase_times(a, b, cuts, names) -> dict:
    """Seconds of [a, b) under each phase."""
    out: dict = {}
    i = bisect.bisect_right(cuts, a) - 1
    while i < len(names) and cuts[i] < b:
        x, y = max(cuts[i], a), min(cuts[i + 1], b)
        if y > x:
            out[names[i]] = out.get(names[i], 0.0) + (y - x) * 1e-9
        i += 1
    return out


def reduce(pd, top: int = 10) -> dict:
    """-> {"modules", "idle_by_phase", "idle_gaps_by_phase"} for the
    traced slice (see the module's docstring); times in seconds."""
    (lo, hi), spans = _host_spans(pd)
    cuts, names = _segments(lo, hi, spans)
    modules: dict = {}
    by_phase: dict = {}
    gaps, n_dev = [], 0
    for p in pd.planes:
        if not p.name.startswith("/device:") or "CPU" in p.name:
            continue
        ivs = []
        steps = []
        for name, ev in trace._events(p):
            if name == MODULES_LINE and lo <= ev.start_ns \
                    and ev.end_ns <= hi:
                steps.append(ev)
            elif name == trace.OPS_LINE:
                a, b = max(ev.start_ns, lo), min(ev.end_ns, hi)
                if b > a:
                    ivs.append((a, b))
        if not ivs:
            continue
        cut = max(b for _, b in ivs)      # where the device's record ends
        for ev in steps:
            if ev.end_ns < cut:
                modules.setdefault(module_name(ev.name), []).append(
                    (ev.end_ns - ev.start_ns) * 1e-9)
        n_dev += 1
        edges = [lo] + [x for iv in trace._union(ivs) for x in iv] + [hi]
        for a, b in zip(edges[0::2], edges[1::2], strict=True):
            if b > a:
                gaps.append((a, b))
                for k, v in _phase_times(a, b, cuts, names).items():
                    by_phase[k] = by_phase.get(k, 0.0) + v
    if n_dev == 0:
        raise ValueError("no operation ran on a device in the traced slice")
    named = []
    for a, b in sorted(gaps, key=lambda g: g[0] - g[1])[:top]:
        share = _phase_times(a, b, cuts, names)
        named.append([max(share, key=share.get), (b - a) * 1e-9])
    return {"modules": modules,
            "idle_by_phase": {k: v / n_dev for k, v in sorted(
                by_phase.items(), key=lambda kv: -kv[1])},
            "idle_gaps_by_phase": named}


def step_ms(reduced: dict, module: str):
    """Median device time of one compiled step's events in the slice, ms;
    None where the slice holds none (or the program names no steps)."""
    times = reduced["modules"].get(module)
    return statistics.median(times) * 1e3 if times else None
