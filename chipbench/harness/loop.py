"""The serving loop that the measured window drives.

The harness owns the clock.  Every request is due at a time of its own,
and the loop submits it then, whatever the engine is doing: an open
loop's sessions start on a schedule; a closed loop's callers start their
next session when their last one finished; a session's next turn is due
``think_s`` after its previous turn finished, with that turn's prompt and
served tokens in front of its own.  Between submissions the loop calls
``Engine.step()`` whenever a request is queued or running, and sleeps
until the next due time when none is.  Nothing is gated on the engine's
step count.

The load starts before the window opens: an open loop's arrivals run
``warm_s`` first, a closed loop waits until every caller has its first
token and then ``warm_s``.  So the window opens on an engine already in
its steady state.  After the window closes the load goes on until every
request of the window has served all its tokens (or ``drain_cap_s``
passes; a request still without a first token then counts as failed), so
that each request's time per output token is taken over its whole output
and not over the part served before the close.

Times are ``time.perf_counter()`` seconds.  The engine stamps
``admitted`` and ``first_token`` relative to its own ``_t0``; they are
moved onto this clock.  A request's last token is stamped at the end of
the step that harvested it.

When traced, the profiler starts as the window opens (which takes some
hundredths of a second) and stops after the drain (which takes most of a
minute, and so stalls nothing that is measured); the span ``cb.window``
marks the window in the trace.
"""
from __future__ import annotations

import contextlib
import dataclasses
import heapq
import itertools
import time
from typing import Dict, List, Optional

import numpy as np

from .traffic import Traffic


@dataclasses.dataclass
class Rec:
    """One request as the harness saw it."""
    idx: int
    prompt: np.ndarray          # the whole prompt submitted
    max_new: int
    due: float                  # when due (open), sent (closed), or its
                                # turn came (sessions)
    submitted: float = 0.0
    admitted: float = 0.0
    first: float = 0.0
    last: float = 0.0
    n_tokens: int = 0
    in_window: bool = False
    client: int = -1
    session: int = 0
    turn: int = 0
    adapter_id: Optional[int] = None

    @property
    def prompt_len(self) -> int:
        return len(self.prompt)

    @property
    def ttft(self) -> float:
        return self.first - self.due

    @property
    def tpot(self) -> float:
        return (self.last - self.first) / (self.n_tokens - 1)

    @property
    def finished(self) -> bool:
        return self.n_tokens >= self.max_new


@dataclasses.dataclass
class StepRec:
    t0: float
    t1: float
    ev_lo: int                  # engine.trace[ev_lo:ev_hi] is this step's
    ev_hi: int


@dataclasses.dataclass
class Served:
    recs: List[Rec]
    steps: List[StepRec]
    t_open: float
    t_close: float
    tokens_in_window: int
    lateness: List[float]       # submitted - due, every request
    drained: bool = True


def _spans(on: bool):
    """``jax.profiler.TraceAnnotation`` when tracing, else nothing."""
    if not on:
        return lambda name, **kw: contextlib.nullcontext()
    import jax
    return jax.profiler.TraceAnnotation


def wrap_engine_fns(engine, span) -> None:
    """Put a host span around each call of the engine's jitted steps,
    tagged with the index its trace event will have."""
    prefill, decode = engine.prefill_fn, engine.decode_fn

    def traced_prefill(*args):
        with span("cb.prefill", ev=len(engine.trace)):
            return prefill(*args)

    def traced_decode(*args):
        with span("cb.decode", ev=len(engine.trace)):
            return decode(*args)

    engine.prefill_fn, engine.decode_fn = traced_prefill, traced_decode


def serve(engine, traffic: Traffic, seconds: float, drain_cap_s: float,
          *, trace: Optional[Dict] = None) -> Served:
    """Drive ``engine`` with ``traffic`` for a window of ``seconds``.

    ``trace`` (``{"start", "stop"}``) starts the profiler as the window
    opens and stops it after the drain."""
    from repro.engine import Request      # the system under test

    span = _spans(trace is not None)
    if trace is not None:
        wrap_engine_fns(engine, span)
    clock = time.perf_counter
    recs: Dict[int, Rec] = {}
    inflight: Dict[int, Rec] = {}
    steps: List[StepRec] = []
    lateness: List[float] = []
    due_heap: list = []                   # (due, order, turn, prev, client)
    order = itertools.count()
    sessions = {g.session: s for s in traffic.sessions for g in s.turns}
    closed = traffic.loop == "closed"
    next_session = 0
    window_open: Optional[float] = None
    t_close: Optional[float] = None
    tokens_at_open = tokens_at_close = 0
    total_tokens = 0
    window_span = None

    def push(due, g, prev, client):
        heapq.heappush(due_heap, (due, next(order), g, prev, client))

    def start_session(due, client):
        nonlocal next_session
        if next_session >= len(traffic.sessions):
            raise RuntimeError("closed-loop session pool ran out; raise "
                               "pool_per_client")
        push(due, traffic.sessions[next_session].turns[0], None, client)
        next_session += 1

    def submit_due(now):
        while due_heap and due_heap[0][0] <= now:
            due, _, g, prev, client = heapq.heappop(due_heap)
            prompt = g.prompt
            if prev is not None:
                served = np.asarray(engine.results[prev.idx].tokens,
                                    np.int32)
                prompt = np.concatenate([prev.prompt, served, g.prompt])
            r = Rec(g.idx, prompt, g.max_new, due, submitted=clock(),
                    client=client, session=g.session, turn=g.turn,
                    adapter_id=g.adapter_id)
            r.in_window = (window_open is not None
                           and window_open <= due < window_open + seconds)
            engine.submit(Request(rid=g.idx, prompt=prompt,
                                  max_new=g.max_new,
                                  adapter_id=g.adapter_id))
            recs[g.idx] = inflight[g.idx] = r
            lateness.append(r.submitted - due)

    def harvest(t_end):
        nonlocal total_tokens
        t0 = engine._t0
        for rid, r in list(inflight.items()):
            res = engine.results.get(rid)
            if res is None:
                continue
            n = len(res.tokens)
            if r.n_tokens == 0 and n:
                r.admitted = t0 + res.admitted
                r.first = t0 + res.first_token
                r.last = r.first
            if n > r.n_tokens:
                total_tokens += n - r.n_tokens
                if r.n_tokens:
                    r.last = t_end
                r.n_tokens = n
            if n >= r.max_new:
                del inflight[rid]
                turns = sessions[r.session].turns
                if r.turn + 1 < len(turns):
                    push(t_end + traffic.think_s, turns[r.turn + 1], r,
                         r.client)
                elif closed:
                    start_session(t_end + traffic.think_s, r.client)

    def step():
        t0 = clock()
        lo = len(engine.trace)
        with span("cb.step"):
            engine.step()
        t1 = clock()
        harvest(t1)
        steps.append(StepRec(t0, t1, lo, len(engine.trace)))

    def window_done():
        return all(r.finished for r in recs.values() if r.in_window)

    t_load = clock()
    if closed:
        for c in range(traffic.clients):
            start_session(t_load, c)
        warm_until = None                 # set once every caller is served
    else:
        for s in traffic.sessions:
            push(t_load + s.due_s, s.turns[0], None, -1)
        warm_until = t_load + traffic.warm_s

    while True:
        now = clock()
        if window_open is None:
            if warm_until is None and recs and all(
                    r.n_tokens for r in recs.values()):
                warm_until = now + traffic.warm_s
            if warm_until is not None and now >= warm_until:
                if trace is not None:
                    trace["start"]()
                    now = clock()
                window_open, tokens_at_open = now, total_tokens
                if trace is not None:
                    window_span = span("cb.window")
                    window_span.__enter__()
        elif t_close is None and now >= window_open + seconds:
            t_close, tokens_at_close = now, total_tokens
            if window_span is not None:
                window_span.__exit__(None, None, None)
        if t_close is not None and (window_done()
                                    or now >= t_close + drain_cap_s):
            break
        with span("cb.submit"):
            submit_due(now)
        if not engine.done:
            step()
            continue
        # nothing queued or running: wait for the next due request
        wake = due_heap[0][0] if due_heap else now + 1e-3
        if window_open is None and warm_until is not None:
            wake = min(wake, warm_until)
        elif window_open is not None and t_close is None:
            wake = min(wake, window_open + seconds)
        with span("cb.wait"):
            time.sleep(max(0.0, wake - clock()))
    if trace is not None:
        trace["stop"]()
    return Served(
        recs=sorted(recs.values(), key=lambda r: r.idx), steps=steps,
        t_open=window_open, t_close=t_close,
        tokens_in_window=tokens_at_close - tokens_at_open,
        lateness=lateness, drained=window_done())
