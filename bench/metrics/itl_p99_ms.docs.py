"""Inter-token latency, 99th percentile, ms, over all gaps in the window
between consecutive output tokens of one request, in the document cells.

There the tail is the gap across a tick that carries prefill chunks, and it
swings from run to run with how fast the host keeps up, so it stands per
layer beside the cell's throughput rather than end to end."""
from bench.metrics.itl_p99_ms import read  # noqa: F401
