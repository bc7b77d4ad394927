"""Kernels: the paged prefill kernel's least time over its device time.

Each ``jit_prefill`` execution in the trace is paired with the engine
step that dispatched it, whose ``prefill_chunk`` event gives the chunk's
start and valid rows.  Its least time is that of every call the
architecture makes of the kernel (its ``kernel_calls``), each the larger
of the call's FLOPs over the bf16 peak and its bytes over HBM bandwidth;
its device time is that of the kernel's events inside the execution."""
from chipbench.harness import roofline as R
from chipbench.harness import xtrace as X

KERNEL = "paged_prefill"


def read(run):
    if run.trace is None:
        return None
    least = spent = 0.0
    for mod, ev in X.match_modules(run.trace, "jit_prefill", "cb.prefill"):
        event = run.events[ev]
        if event.kind != "prefill_chunk":
            return None
        ops = X.kernel_ops(run.trace, KERNEL, mod.start, mod.end)
        if not ops:
            continue
        least += R.calls_least_time(run.architecture.kernel_calls(
            run.arch, KERNEL, event.past_len, event.chunk,
            kv_bytes=run.kv_bytes), run.peaks)
        spent += sum(e.dur for e in ops) / 1e9
    if spent <= 0 or least <= 0:
        return None
    return 100.0 * least / spent
