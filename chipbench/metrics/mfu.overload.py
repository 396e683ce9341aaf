"""The whole step's share of the chip's peak (``mfu``), in the cells
judged on throughput."""
from chipbench.metrics.mfu import read  # noqa: F401
