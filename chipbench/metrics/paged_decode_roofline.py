"""Kernels: the paged decode kernel's least time over its device time.

Each ``jit_decode`` execution in the trace is paired with the engine
step that dispatched it, whose ``decode_block`` event gives every slot's
context at block start.  Its least time is, per fused token step and per
layer, the larger of the call's FLOPs over the bf16 peak and its bytes
over HBM bandwidth (``harness.roofline.paged_decode``, live keys only);
its device time is that of the kernel's events inside the execution."""
from chipbench.harness import roofline as R
from chipbench.harness import xtrace as X


def read(run):
    if run.trace is None:
        return None
    L, n_steps = run.arch["n_layers"], run.engine["decode_block"]
    least = spent = 0.0
    for mod, ev in X.match_modules(run.trace, "jit_decode", "cb.decode"):
        event = run.events[ev]
        if event.kind != "decode_block":
            return None
        ops = X.kernel_ops(run.trace, "paged_decode", mod.start, mod.end)
        if not ops:
            continue
        for ctx in R.decode_block_contexts(event.slots, n_steps):
            if ctx:
                least += L * R.least_time(
                    *R.paged_decode(run.arch, ctx, run.kv_bytes),
                    run.peaks)[0]
        spent += sum(e.dur for e in ops) / 1e9
    if spent <= 0 or least <= 0:
        return None
    return 100.0 * least / spent
