"""Queue wait, 90th percentile, ms: from ``engine.submit()`` to the
request's first admission into a slot, by the program's own stamps
(``Request.t_submit``, ``Request.t_admitted``, both ``time.perf_counter``),
over the requests due in the window that were admitted.  The scheduler's
share of the time to first token; the rest is chunked prefill.  None for a
program that does not stamp admissions."""
from bench.loop import percentile


def read(obs):
    waits = []
    for r in obs.records:
        t_admitted = getattr(r.req, "t_admitted", None)
        if obs.in_window(r.due) and t_admitted is not None:
            waits.append((t_admitted - r.req.t_submit) * 1e3)
    return percentile(waits, 90)
