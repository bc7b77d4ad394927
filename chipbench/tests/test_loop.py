"""The serving loop drains the window: every request due inside it is
served in full, so its time per output token spans its whole output."""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, List

import pytest

from chipbench.harness import loop, traffic as T
from chipbench.metrics import tpot_p95_ms_sched


@dataclasses.dataclass
class _Result:
    tokens: List[int]
    admitted: float = 0.0
    first_token: float = 0.0


class _FakeEngine:
    """Serves every submitted request ``per_step`` tokens a step, one
    millisecond a step: what ``loop.serve`` sees of ``repro.engine``."""

    def __init__(self, per_step: int = 1):
        self._t0 = time.perf_counter()
        self.per_step = per_step
        self.trace: list = []
        self.results: Dict[int, _Result] = {}
        self._want: Dict[int, int] = {}

    def submit(self, req):
        self._want[req.rid] = req.max_new

    @property
    def done(self) -> bool:
        return all(len(self.results.get(rid, _Result([])).tokens) >= n
                   for rid, n in self._want.items())

    def step(self):
        time.sleep(1e-3)
        now = time.perf_counter() - self._t0
        for rid, n in self._want.items():
            res = self.results.setdefault(rid, _Result([], now, now))
            k = min(self.per_step, n - len(res.tokens))
            res.tokens += [0] * k


@pytest.mark.parametrize("loop_kind", ["poisson", "closed"])
def test_window_requests_are_served_in_full(loop_kind):
    arrival = ({"kind": "poisson", "rate_per_s": 100.0} if loop_kind ==
               "poisson" else {"kind": "closed", "clients": 3,
                               "pool_per_client": 200})
    mix = {"arrival": arrival, "warm_s": 0.05,
           "prompt": {"dist": "fixed", "value": 4},
           "output": {"dist": "lognormal", "median": 24, "sigma": 0.5,
                      "min": 12, "max": 40}}
    tr = T.generate(mix, 11, 0.2, 100, 5.0)
    served = loop.serve(_FakeEngine(), tr, 0.2, 5.0)
    win = [r for r in served.recs if r.in_window]
    assert win and served.drained
    assert all(r.n_tokens == r.max_new for r in win)


def test_tpot_tail_reader_reads_the_window():
    recs = [loop.Rec(i, None, 9, 0.0, first=0.0, last=0.008 * (i + 1),
                     n_tokens=9, in_window=i < 20) for i in range(30)]
    served = loop.Served(recs, [], 0.0, 1.0, 0, [])
    run = dataclasses.make_dataclass("R", ["served"])(served)
    run.window_recs = lambda: [r for r in served.recs if r.in_window]
    got = tpot_p95_ms_sched.read(run)
    assert got == pytest.approx(1e3 * T.percentile(
        [r.tpot for r in recs[:20]], 95))
    run.window_recs = lambda: []
    assert tpot_p95_ms_sched.read(run) is None
