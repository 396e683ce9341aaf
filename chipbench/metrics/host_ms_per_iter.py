"""Mean host time per engine iteration in the window: the engine's step
less the backend's call (scheduler, bookkeeping, metrics)."""


def read(run):
    t0, t1 = run.window
    execs = [e for e in run.execs if t0 <= e.end < t1]
    if not run.steps or not execs:
        return None
    step_s = sum(s.end - s.start for s in run.steps)
    exec_s = sum(e.end - e.start for e in execs)
    return (step_s - exec_s) / len(run.steps) * 1e3
