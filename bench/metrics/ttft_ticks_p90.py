"""Engine ticks from a request's due time to its first token, 90th
percentile, over the requests due in the window: the scheduler's share of
the time to first token, free of how long each tick took.  Counts
``EngineStats.ticks``."""
from bench.loop import percentile


def read(obs):
    n = [r.first_tick - r.submit_tick for r in obs.records
         if obs.in_window(r.due) and r.first_tick >= 0]
    return percentile(n, 90)
