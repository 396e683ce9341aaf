"""90th percentile over the window's requests of each one's mean gap
between output tokens: the requests whose decoding shared most
iterations with prefill or waited on the controller."""
from chipbench.metrics._common import pct, tpots_ms


def read(run):
    return pct(tpots_ms(run), 90)
