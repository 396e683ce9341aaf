"""90th percentile of time to first token, from when each request due in
the window was due."""
from chipbench.metrics._common import pct, ttfts_ms


def read(run):
    v = ttfts_ms(run)
    return None if v is None else pct(v, 90)
