"""90th percentile of the wait from each window request's due time to
the start of the first engine step whose plan held it (engine and
scheduler)."""
from chipbench.metrics._common import pct


def read(run):
    waits = [(r.scheduled - r.due) * 1e3 for r in run.reqs
             if r.scheduled is not None]
    return pct(waits, 90)
