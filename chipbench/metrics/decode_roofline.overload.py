"""The decode call's share of its roofline (``decode_roofline``), in the
cells judged on throughput."""
from chipbench.metrics.decode_roofline import read  # noqa: F401
