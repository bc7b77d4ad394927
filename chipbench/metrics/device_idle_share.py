"""Device: share of the traced time with a request in flight in which no
operation ran on the chip, in %.  The harness waits (``cb.wait``) only
when nothing is queued or running; the rest of the traced window counts."""
from chipbench.harness import xtrace as X


def read(run):
    if run.trace is None:
        return None
    live = X.in_flight(run.trace)
    total = sum(b - a for a, b in live)
    if total <= 0:
        return None
    busy = sum(X.covered(run.trace.devices[0].busy, a, b) for a, b in live)
    return 100.0 * (1.0 - busy / total)
