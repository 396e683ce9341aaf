"""The decode call's share of its roofline: the least time the chip needs
for the work the served sequences need (``chipbench.roofline``), over
the decode program's device time per call, in the traced window."""
from chipbench import roofline
from chipbench.metrics._common import program, traced_execs


def read(run):
    p = program(run, "decode_step")
    calls = [e for e in traced_execs(run) if e.contexts]
    if p is None or not calls:
        return None
    least = sum(roofline.least_time(
        *roofline.decode_work(run.shape, e.contexts), run.device_kind)
        for e in calls) / len(calls)
    return 100.0 * least / (p["device_s"] / p["calls"])
