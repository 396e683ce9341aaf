"""Mean share of the device batch that the window's decode calls served
(running sequences over the batch): how full the scheduler keeps the
chip."""


def read(run):
    t0, t1 = run.window
    calls = [len(e.contexts) for e in run.execs
             if t0 <= e.end < t1 and e.contexts]
    return 100.0 * sum(calls) / (len(calls) * run.batch) if calls else None
