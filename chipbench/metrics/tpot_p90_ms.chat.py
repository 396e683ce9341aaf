"""``tpot_p90_ms`` in a cell whose requests live long enough that one
host stall reaches more than a tenth of them, so that the number swings
with the machine: the requests whose decoding shared most iterations
with prefill, read beside the median."""
from chipbench.metrics.tpot_p90_ms import read  # noqa: F401
