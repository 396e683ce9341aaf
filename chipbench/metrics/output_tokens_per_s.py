"""Output tokens that the engine produced in the window, over the
window's length: the served throughput, for a cell offered more load
than the chip sustains."""


def read(run):
    t0, t1 = run.window
    return sum(s.tokens for s in run.steps) / (t1 - t0)
