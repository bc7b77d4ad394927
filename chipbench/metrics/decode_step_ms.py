"""Jitted steps: device time of one ``jit_decode`` program execution
over the tokens it fuses (``decode_block``), averaged over the window,
from the trace, in ms per token step."""
from chipbench.harness import xtrace as X


def read(run):
    if run.trace is None:
        return None
    mods = X.modules_named(run.trace, "jit_decode")
    if not mods:
        return None
    n = run.engine["decode_block"]
    return sum(m.dur for m in mods) / len(mods) / n / 1e6
