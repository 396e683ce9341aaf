"""The open-loop client on the wall clock.

It submits each request when it falls due on its schedule, so the engine
ingests it on its next step; calls ``engine.step()`` and then
``policy.maybe_act(engine)`` while there is work; sleeps until the next
due time when there is none; and after each step records the wall time
of every new token of every request. The engine's own (simulated) clock
feeds no metric.

It times each backend call, for the per-layer metrics and the window's
summary on standard error. With ``spans`` on, it also marks what the
host is doing with profiler annotations (``submit``, ``engine.step``,
``backend.execute``, ``policy``, ``wait_for_request``).
"""
from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Callable, Dict, List, Optional

from repro.serving import Request

#: How long past the window's close the run waits for the window's
#: requests to finish. A request still unfinished then never came.
DRAIN_CAP_S = 120.0

SPANS = ("submit", "engine.step", "backend.execute", "policy",
         "wait_for_request")

_NO_SPAN = contextlib.nullcontext()


@dataclasses.dataclass
class Req:
    """What the client saw of one request (seconds on its clock)."""
    due: float
    output_len: int
    request: Request
    submit: float = 0.0
    scheduled: Optional[float] = None   # start of the first step holding it
    first_token: Optional[float] = None
    last_token: Optional[float] = None
    tokens: int = 0


@dataclasses.dataclass
class Step:
    start: float
    end: float
    tokens: int


@dataclasses.dataclass
class ExecCall:
    """One ``backend.execute`` call."""
    start: float
    end: float
    contexts: List[int]        # cache length of each decoded sequence
    prefill_tokens: int        # prompt tokens the scheduler planned


@dataclasses.dataclass
class PolicyCall:
    at: float
    seconds: float
    decided: bool


class Client:
    """Drives one engine under one arrival schedule."""

    def __init__(self, engine, policy, arrivals, template_frac: float,
                 spans: bool = False):
        self.engine = engine
        self.policy = policy
        self.arrivals = arrivals
        self.template_frac = template_frac
        self.spans = spans
        self.next = 0
        self.reqs: Dict[int, Req] = {}
        self.steps: List[Step] = []
        self.execs: List[ExecCall] = []
        self.policy_calls: List[PolicyCall] = []
        self.t0 = time.perf_counter()
        self._wrap_execute()

    # ------------------------------------------------------------------
    def now(self) -> float:
        return time.perf_counter() - self.t0

    def span(self, name: str):
        if not self.spans:
            return _NO_SPAN
        import jax
        return jax.profiler.TraceAnnotation(name)

    def _wrap_execute(self) -> None:
        backend = self.engine.backend
        real = backend.execute

        def execute(plan, f_mhz):
            ctx = [r.context_len for r in plan.decode]
            with self.span("backend.execute"):
                t = self.now()
                out = real(plan, f_mhz)
                self.execs.append(ExecCall(t, self.now(), ctx,
                                           plan.prefill_tokens))
            return out

        backend.execute = execute

    # ------------------------------------------------------------------
    def submit_due(self) -> None:
        now = self.now()
        arr = self.arrivals
        if self.next >= len(arr) or arr[self.next].due > now:
            return
        with self.span("submit"):
            eng = self.engine
            while self.next < len(arr) and arr[self.next].due <= now:
                a = arr[self.next]
                r = Request(arrival_time=eng.clock, prompt_len=a.prompt,
                            output_len=a.output, template_id=a.template,
                            template_frac=self.template_frac)
                eng.submit([r])
                self.reqs[r.request_id] = Req(a.due, a.output, r,
                                              submit=self.now())
                self.next += 1

    def step(self) -> None:
        """One engine iteration and one policy call, or a sleep until the
        next arrival when the engine has no work."""
        self.submit_due()
        eng = self.engine
        if not eng.has_work:
            due = (self.arrivals[self.next].due
                   if self.next < len(self.arrivals) else self.now() + 0.01)
            with self.span("wait_for_request"):
                time.sleep(max(0.0, due - self.now()))
            return
        with self.span("engine.step"):
            ts = self.now()
            finished = eng.step()
            te = self.now()
        tokens = 0
        for r in list(eng.sched.running.values()) + finished:
            rec = self.reqs[r.request_id]
            if rec.scheduled is None:
                rec.scheduled = ts
            if r.generated > rec.tokens:
                tokens += r.generated - rec.tokens
                if rec.first_token is None:
                    rec.first_token = te
                rec.last_token = te
                rec.tokens = r.generated
        self.steps.append(Step(ts, te, tokens))
        if self.policy is not None:
            with self.span("policy"):
                t = time.perf_counter()
                f = self.policy.maybe_act(eng)
                self.policy_calls.append(PolicyCall(
                    te, time.perf_counter() - t, f is not None))

    def run_until(self, done: Callable[[], bool]) -> None:
        while not done():
            self.step()

    # ------------------------------------------------------------------
    def due_in(self, t0: float, t1: float) -> List[Req]:
        return [r for r in self.reqs.values() if t0 <= r.due < t1]

    def all_finished(self, reqs: List[Req]) -> bool:
        return all(r.tokens >= r.output_len for r in reqs)
