"""Device time of one decode call: the decode program's device seconds
in the traced window over its calls."""
from chipbench.metrics._common import program


def read(run):
    p = program(run, "decode_step")
    return None if p is None else p["device_s"] / p["calls"] * 1e3
