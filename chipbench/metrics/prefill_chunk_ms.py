"""Jitted steps: mean device time of one ``jit_prefill`` program
execution (one prompt chunk through every layer) in the window, from the
trace, in ms."""
from chipbench.harness import xtrace as X


def read(run):
    if run.trace is None:
        return None
    mods = X.modules_named(run.trace, "jit_prefill")
    if not mods:
        return None
    return sum(m.dur for m in mods) / len(mods) / 1e6
