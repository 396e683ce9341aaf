"""Median over the window's requests of each one's mean gap between
output tokens (first to last token, over at least seven gaps)."""
from chipbench.metrics._common import pct, tpots_ms


def read(run):
    return pct(tpots_ms(run), 50)
