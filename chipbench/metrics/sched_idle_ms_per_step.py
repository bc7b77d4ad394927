"""Scheduler: device-0 time inside ``engine.step`` spans that no program
execution covers, per step, in ms: the idle that the scheduler's own host
work leaves between programs
(``harness.engine_spans.sched_idle_ms_per_step``)."""
from chipbench.harness import engine_spans as E


def read(run):
    if run.trace is None:
        return None
    return E.sched_idle_ms_per_step(run.trace, run.trace.engine_spans)
