"""Kernels: the paged prefill kernel's least time over its device time.

Each ``jit_prefill`` execution in the trace is paired with the engine
step that dispatched it, whose ``prefill_chunk`` event gives the chunk's
start and valid rows.  Its least time is, per layer, the larger of the
call's FLOPs over the bf16 peak and its bytes over HBM bandwidth
(``harness.roofline.paged_prefill``); its device time is that of the
kernel's events inside the execution."""
from chipbench.harness import roofline as R
from chipbench.harness import xtrace as X


def read(run):
    if run.trace is None:
        return None
    L = run.arch["n_layers"]
    least = spent = 0.0
    for mod, ev in X.match_modules(run.trace, "jit_prefill", "cb.prefill"):
        event = run.events[ev]
        if event.kind != "prefill_chunk":
            return None
        ops = X.kernel_ops(run.trace, "paged_prefill", mod.start, mod.end)
        if not ops:
            continue
        least += L * R.least_time(
            *R.paged_prefill(run.arch, event.past_len, event.chunk,
                             run.kv_bytes), run.peaks)[0]
        spent += sum(e.dur for e in ops) / 1e9
    if spent <= 0 or least <= 0:
        return None
    return 100.0 * least / spent
