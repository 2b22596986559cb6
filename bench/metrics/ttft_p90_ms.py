"""Time to first token, 90th percentile, ms: from when each request was
due to when its first token reached the host, over the requests due in the
window (open loop).  Requests that never got a first token are counted as
failed by the harness, not here."""
from bench.loop import percentile


def read(obs):
    ttft = [(r.token_t[0] - r.due) * 1e3 for r in obs.records
            if obs.in_window(r.due) and r.token_t]
    return percentile(ttft, 90)
