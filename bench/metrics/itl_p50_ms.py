"""Inter-token latency, 50th percentile, ms, over all gaps in the window
between consecutive output tokens of one request."""
from bench.loop import percentile
from bench.metrics._gaps import gaps_ms


def read(obs):
    return percentile(gaps_ms(obs), 50)
