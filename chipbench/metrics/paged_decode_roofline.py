"""Kernels: the paged decode kernel's least time over its device time.

Each ``jit_decode`` execution in the trace is paired with the engine
step that dispatched it, whose ``decode_block`` event gives every slot's
context at block start.  Its least time is, per fused token step, that of
every call the architecture makes of the kernel (its ``kernel_calls``,
live keys only), each the larger of the call's FLOPs over the bf16 peak
and its bytes over HBM bandwidth; its device time is that of the
kernel's events inside the execution."""
from chipbench.harness import roofline as R
from chipbench.harness import xtrace as X

KERNEL = "paged_decode"


def read(run):
    if run.trace is None:
        return None
    n_steps = run.engine["decode_block"]
    least = spent = 0.0
    for mod, ev in X.match_modules(run.trace, "jit_decode", "cb.decode"):
        event = run.events[ev]
        if event.kind != "decode_block":
            return None
        ops = X.kernel_ops(run.trace, KERNEL, mod.start, mod.end)
        if not ops:
            continue
        for ctx in R.decode_block_contexts(event.slots, n_steps):
            if ctx:
                least += R.calls_least_time(run.architecture.kernel_calls(
                    run.arch, KERNEL, ctx, kv_bytes=run.kv_bytes), run.peaks)
        spent += sum(e.dur for e in ops) / 1e9
    if spent <= 0 or least <= 0:
        return None
    return 100.0 * least / spent
