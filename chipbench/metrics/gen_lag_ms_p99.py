"""99th percentile of how late the client submitted the window's
requests (submit time minus due time): a starved generator is not a
fast server."""
from chipbench.metrics._common import pct


def read(run):
    return pct([(r.submit - r.due) * 1e3 for r in run.reqs], 99)
