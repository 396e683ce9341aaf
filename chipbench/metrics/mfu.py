"""Model FLOPs of every decode and prefill call in the traced window
(served sequences and prompt tokens only) over the window's length
times the chip's peak bf16 FLOP/s."""
from chipbench import roofline
from chipbench.metrics._common import traced_execs


def read(run):
    flops = 0.0
    for e in traced_execs(run):
        flops += roofline.decode_work(run.shape, e.contexts)[0]
        if e.prefill_tokens:
            flops += roofline.prefill_flops(
                run.shape, min(e.prefill_tokens, run.prefill_max))
    peak = roofline.peaks(run.device_kind)["bf16_flops"]
    return 100.0 * flops / (run.trace["window_s"] * peak)
