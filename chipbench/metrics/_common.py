"""Arithmetic shared by the metric readers. A reader is
``chipbench/metrics/<metric>.py`` with ``read(run) -> float | None``;
``None`` (nothing to read) leaves the metric out of the result line."""
from __future__ import annotations

from typing import List, Optional

import numpy as np

#: a request's time per output token is read over at least this many
#: gaps between its tokens, so that it spans some hundreds of
#: milliseconds of the host's clock (off by up to about half a
#: millisecond at each end)
TPOT_MIN_GAPS = 7


def pct(values: List[float], q: float) -> Optional[float]:
    return float(np.percentile(values, q)) if values else None


def tpots_ms(run) -> List[float]:
    """Each window request's mean gap between its output tokens."""
    return [(r.last_token - r.first_token) / (r.tokens - 1) * 1e3
            for r in run.reqs
            if r.tokens - 1 >= TPOT_MIN_GAPS and r.first_token is not None]


def ttfts_ms(run) -> Optional[List[float]]:
    """Time to first token from when each window request was due; None
    when one never had a first token."""
    if any(r.first_token is None for r in run.reqs):
        return None
    return [(r.first_token - r.due) * 1e3 for r in run.reqs]


def traced_execs(run):
    t0, t1 = run.traced
    return [e for e in run.execs if t0 <= e.start and e.end <= t1]


def program(run, needle: str) -> Optional[dict]:
    """Device seconds and calls of the traced program whose name holds
    ``needle``."""
    hits = [v for k, v in run.trace["programs"].items() if needle in k]
    if not hits or not sum(h["calls"] for h in hits):
        return None
    return {"device_s": sum(h["device_s"] for h in hits),
            "calls": sum(h["calls"] for h in hits)}
