"""Drive the engine with a traffic mix and record what a user would see.

Open loop: requests are submitted when due, whether or not the engine has
caught up; a request's time to first token runs from when it was due.
Closed loop: a fixed number of requests are outstanding, and each one that
completes is replaced at once.  The harness times everything on its own
host clock, after each ``tick()``: a token counts as served when the host
sees it in ``Request.out_tokens``.  Nothing here changes what the engine
computes.
"""
from __future__ import annotations

import contextlib
import time
from dataclasses import dataclass, field

import numpy as np

from bench import traffic

DRAIN_CAP_S = 60.0     # after the window: wait this long at most for the
                       # first tokens of requests due inside it


@dataclass
class Record:
    job: traffic.Job
    req: object                 # the program's Request
    due: float                  # host time the request was due
    submitted: float = 0.0
    submit_tick: int = 0
    token_t: list = field(default_factory=list)   # host time of each token
    first_tick: int = -1
    done_t: float = -1.0


@dataclass
class Observation:
    """What one run saw; per-layer and end-to-end readers take it."""
    records: list
    window: tuple               # (start, end) host times
    ticks: list                 # (start, end, busy) per tick, busy: a slot held
    collect_wait_s: float       # growth of EngineStats.collect_wait_s in window
    slots: int
    chunk: int
    lateness_s: list            # submission time minus due time (open loop)
    waiting: list               # (time, admitted-but-waiting requests)
    compiles_in_window: int = 0
    setup_s: float = 0.0
    trace: dict | None = None   # bench.trace.reduce() of the traced slice
    cfgfile: dict = field(default_factory=dict)
    peaks: dict | None = None
    weight_bytes: int = 0

    def in_window(self, t: float) -> bool:
        return self.window[0] <= t < self.window[1]

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    def window_ticks(self):
        return [(a, b, busy) for a, b, busy in self.ticks
                if self.window[0] <= a and b <= self.window[1]]


class CompileCounter:
    """Counts lowerings to XLA (new compiled programs or cache loads)."""
    EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"

    def __init__(self):
        import jax
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event == self.EVENT:
            self.n += 1


def _span(name: str, on: bool):
    if not on:
        return contextlib.nullcontext()
    import jax
    return jax.profiler.TraceAnnotation(name)


def run(engine, jobs: list, mix: dict, seconds: float, *, tracer=None,
        counter: CompileCounter | None = None, min_finished: int = 0,
        clock=time.perf_counter) -> Observation:
    """Serve ``jobs`` through ``engine`` for a window of ``seconds``.

    After the window the loop runs on, for ``DRAIN_CAP_S`` at most, until
    every request due in it has its first token (open loop) and at least
    ``min_finished`` greedy requests have finished, for the check to
    sample; a closed loop submits nothing new then.  ``tracer``: None, or
    an object with ``start()``/``stop()`` that the loop calls around the
    last ``tracer.seconds`` of the window."""
    from repro.serving import Request
    open_loop = mix["loop"] == "open"
    annotate = tracer is not None
    recs: list = []
    live: list = []
    lateness, waiting = [], []
    ticks = []
    stats = engine.stats
    nxt = 0
    t_origin = clock()
    if open_loop:
        w0 = t_origin + float(mix["lead_in_s"])
        w1 = w0 + seconds
    else:
        w0 = w1 = float("inf")
    cw0 = cw1 = None
    n_compiles0 = n_compiles1 = None
    trace_at = None
    trace_on = False

    def submit(job, due, now):
        req = Request(rid=job.index, prompt=job.prompt,
                      max_new_tokens=job.max_new_tokens)
        with _span("submit", annotate):
            engine.submit(req)
        rec = Record(job=job, req=req, due=due, submitted=now,
                     submit_tick=stats.ticks)
        recs.append(rec)
        live.append(rec)
        if open_loop:
            lateness.append(now - due)

    def observe(now) -> int:
        """Record tokens the host now sees; -> requests that finished."""
        n_done = 0
        for rec in list(live):
            n = len(rec.req.out_tokens)
            if n > len(rec.token_t):
                if rec.first_tick < 0:
                    rec.first_tick = stats.ticks
                rec.token_t.extend([now] * (n - len(rec.token_t)))
            if rec.req.done:
                rec.done_t = now
                live.remove(rec)
                n_done += 1
        return n_done

    if not open_loop:
        for _ in range(int(mix["outstanding"])):
            submit(jobs[nxt], clock(), clock())
            nxt += 1

    while True:
        now = clock()
        if open_loop:
            while nxt < len(jobs) and t_origin + jobs[nxt].due_s <= now:
                submit(jobs[nxt], t_origin + jobs[nxt].due_s, now)
                nxt += 1
        if cw0 is None and now >= w0:
            cw0 = stats.collect_wait_s
            n_compiles0 = counter.n if counter else 0
            trace_at = w1 - (tracer.seconds if tracer else 0)
        if tracer is not None and trace_at is not None and not trace_on \
                and not tracer.done and now >= trace_at:
            tracer.start()
            trace_on = True
        if now >= w1:
            if cw1 is None:
                cw1 = stats.collect_wait_s
                n_compiles1 = counter.n if counter else 0
            if trace_on:
                # the slice ends with the window: stopping the profiler
                # stalls the host, and only the drain after it sees that
                tracer.stop()
                trace_on = False
            pending = open_loop and any(r.due < w1 and not r.token_t
                                        for r in recs)
            short = sum(1 for r in recs if r.job.greedy and r.req.done) \
                < min_finished
            if not (pending or short) or now >= w1 + DRAIN_CAP_S:
                break
        busy = any(a is not None for a in engine.admissions)
        if not busy and not engine.has_pending():
            if open_loop and nxt < len(jobs):
                wait = t_origin + jobs[nxt].due_s - clock()
                if wait > 0:
                    with _span("generator-wait", annotate):
                        time.sleep(wait)
                continue
            if not open_loop and nxt >= len(jobs):
                break
        t0 = clock()
        with _span("tick", annotate):
            engine.tick()
        t1 = clock()
        ticks.append((t0, t1, busy))
        n_done = observe(t1)
        if open_loop:
            in_slot = {a.req.rid for a in engine.admissions if a is not None}
            waiting.append((t1, sum(1 for r in live
                                    if r.req.rid not in in_slot)))
        else:
            for _ in range(n_done):
                if nxt < len(jobs) and t1 < w1:
                    submit(jobs[nxt], t1, t1)
                    nxt += 1
            if w0 == float("inf") and all(a is not None
                                          for a in engine.admissions):
                w0, w1 = t1, t1 + seconds
    if trace_on:
        tracer.stop()
    if cw0 is None:
        raise RuntimeError("the window never opened: no slot filled")
    if cw1 is None:
        cw1 = stats.collect_wait_s
        n_compiles1 = counter.n if counter else 0
    obs = Observation(
        records=recs, window=(w0, min(w1, clock())), ticks=ticks,
        collect_wait_s=cw1 - cw0, slots=engine.B,
        chunk=engine.chunk, lateness_s=lateness, waiting=waiting)
    obs.compiles_in_window = n_compiles1 - n_compiles0
    return obs


def percentile(values, q: float):
    """numpy's linear-interpolated percentile, or None with no samples."""
    return float(np.percentile(np.asarray(values, np.float64), q)) \
        if len(values) else None
