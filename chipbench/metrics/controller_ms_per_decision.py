"""Mean host time of the controller's calls in the window that made a
decision (AGFT's features, LinUCB credit, pruning, refinement, select)."""


def read(run):
    t0, t1 = run.window
    calls = [c.seconds for c in run.policy_calls
             if c.decided and t0 <= c.at < t1]
    return sum(calls) / len(calls) * 1e3 if calls else None
