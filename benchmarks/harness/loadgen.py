"""The load generator: one client thread that sends what is due and reads
what has come back, on its own clock.

It knows the system under test only as `submit(prompt, max_new, request_id)`
returning an object with an `out` queue that yields token ids and then None
(what `DecodeEngine.submit` returns, and what `inference/server.py` reads).
Times are the client's: a request is timed from when it was *due*, and its
first token and its end are stamped when this thread reads them.
"""
from __future__ import annotations

import dataclasses
import queue
import time
from typing import Callable, List, Optional

from benchmarks.harness.traffic import Planned

POLL_S = 0.001


@dataclasses.dataclass
class Record:
    plan: Planned
    rid: Optional[str] = None
    sent: Optional[float] = None        # all times: seconds from the opening
    first: Optional[float] = None
    last: Optional[float] = None
    done: bool = False
    error: Optional[str] = None
    tokens: List[int] = dataclasses.field(default_factory=list)
    handle: object = None

    @property
    def ttft_ms(self) -> float:
        return (self.first - self.plan.due) * 1e3

    @property
    def tpot_ms(self) -> float:
        return (self.last - self.first) / (len(self.tokens) - 1) * 1e3

    @property
    def late_ms(self) -> float:
        return (self.sent - self.plan.due) * 1e3


@dataclasses.dataclass
class Outcome:
    records: List[Record]
    t_open: float                       # clock reading at the opening
    seconds: float
    tokens_in_window: int
    # Tokens read after the window's first batch of tokens / the time from
    # that batch to the window's last: a rate that does not jump by a whole
    # decode call's tokens with where the window's edges fall.
    token_rate: Optional[float]
    queue_at_open: int
    queue_at_close: int
    hooks: dict


def drive(submit: Callable, plan: List[Planned], *, seconds: float,
          traced: bool, drain: bool, drain_limit_s: float,
          clock: Callable[[], float] = time.perf_counter,
          sleep: Callable[[float], None] = time.sleep,
          at: Optional[dict] = None) -> Outcome:
    """Send `plan` (sorted by due time) and read answers until the window
    has closed and, with `drain`, every request due in it has ended.

    `at` maps a time in seconds from the opening to a callable run once when
    that time has passed (the opening's and the close's counters, the
    profiler's start and stop).  The window opens `-plan[0].due` seconds
    after this call.  Requests due after the close keep the load up while
    the window's own requests end; they are sent and not measured.
    """
    plan = sorted(plan, key=lambda p: p.due)
    lead_in = max(0.0, -plan[0].due)
    t_open = clock() + lead_in
    records = [Record(p) for p in plan]
    pending_hooks = sorted((at or {}).items())
    hooks_out = {}
    open_recs: List[Record] = []
    nxt = 0
    tokens_in_window = 0
    first_batch = last_batch = None     # (stamp, tokens read so far)
    q_open = q_close = None
    while True:
        now = clock() - t_open
        while pending_hooks and pending_hooks[0][0] <= now:
            t_hook, fn = pending_hooks.pop(0)
            hooks_out[t_hook] = fn()
        while nxt < len(records) and records[nxt].plan.due <= now:
            rec = records[nxt]
            nxt += 1
            rec.rid = f'bench-{nxt}' if traced else None
            try:
                rec.handle = submit(rec.plan.prompt, rec.plan.max_new, rec.rid)
            except Exception as e:  # pylint: disable=broad-except
                rec.error, rec.done = repr(e), True     # refused: a failure
                continue
            rec.sent = clock() - t_open
            open_recs.append(rec)
        stamp = clock() - t_open
        in_window = 0.0 <= stamp < seconds
        before = tokens_in_window
        for rec in open_recs:
            out = rec.handle.out
            while True:
                try:
                    tok = out.get_nowait()
                except queue.Empty:
                    break
                if tok is None:
                    rec.done = True
                    break
                if rec.first is None:
                    rec.first = stamp
                rec.last = stamp
                rec.tokens.append(tok)
                tokens_in_window += in_window
        if tokens_in_window > before:
            last_batch = (stamp, tokens_in_window)
            first_batch = first_batch or last_batch
        if any(r.done for r in open_recs):
            open_recs = [r for r in open_recs if not r.done]
        waiting = sum(1 for r in open_recs if r.first is None)
        if q_open is None and stamp >= 0.0:
            q_open = waiting
        if q_close is None and stamp >= seconds:
            q_close = waiting
        if stamp >= seconds:
            due_open = [r for r in open_recs if r.plan.due < seconds]
            if not drain or not due_open and (
                    nxt >= len(records) or records[nxt].plan.due >= seconds):
                break
            if stamp >= seconds + drain_limit_s:
                break
        sleep(POLL_S)
    for t_hook, fn in pending_hooks:        # a close hook at `seconds`
        hooks_out[t_hook] = fn()
    rate = None
    if first_batch and last_batch[0] > first_batch[0]:
        rate = (last_batch[1] - first_batch[1]) / (last_batch[0] -
                                                   first_batch[0])
    return Outcome(records, t_open, seconds, int(tokens_in_window), rate,
                   q_open or 0, q_close or 0, hooks_out)
