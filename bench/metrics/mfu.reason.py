"""mfu.reason: the whole step's share of the chip's roofline in the
reasoning cell (see ``bench/metrics/_mfu.py``)."""
from bench.metrics._mfu import read  # noqa: F401
