"""Scheduler: host waits on the device (``engine.sync`` spans) per engine
step that dispatched a decode block, over the window, from the engine's
own spans in the trace (``harness.engine_spans.host_syncs_per_step``)."""
from chipbench.harness import engine_spans as E


def read(run):
    if run.trace is None:
        return None
    return E.host_syncs_per_step(run.trace.engine_spans)
