"""Device time of one prefill call: the prefill programs' device seconds
in the traced window over their calls."""
from chipbench.metrics._common import program


def read(run):
    p = program(run, "lambda")
    return None if p is None else p["device_s"] / p["calls"] * 1e3
