"""Scheduler: 95th percentile over the window's requests of the time per
output token after the first, ``(last - first) / (tokens - 1)``, in ms,
from the harness's own records of the run.  The prefills that the
scheduler interleaves with decode blocks set this tail."""
from chipbench.harness.traffic import percentile


def read(run):
    tpots = [r.tpot for r in run.window_recs() if r.n_tokens > 1]
    if not tpots:
        return None
    return 1e3 * percentile(tpots, 95)
