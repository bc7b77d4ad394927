"""The engine's own host spans read against the device trace.

``repro.engine.scheduler`` opens profiler spans named ``engine.*`` around
its host work: ``engine.step`` (one ``Engine.step()``), ``engine.admit``,
``engine.prefill`` / ``engine.decode`` / ``engine.verify`` (one jitted
dispatch each, with its trace event's index ``ev``), ``engine.harvest``,
``engine.free``, and ``engine.sync`` (each point where the host waits for
the device, with ``what`` it reads).  They lie on the profiler's host
plane, on the device trace's clock; ``xtrace.reduce`` keeps those that
start in the window, by start, in ``Reduced.engine_spans``.

From them (the readers ``chipbench/metrics/host_syncs_per_step.py`` and
``sched_idle_ms_per_step.py``, and ``run.py``'s ``[engine_idle]`` line):

- ``host_syncs_per_step``: ``engine.sync`` spans per ``engine.step`` that
  dispatched a decode block (held an ``engine.decode``);
- ``sched_idle_ms_per_step``: device-0 time inside ``engine.step`` spans
  that no program execution (``XLA Modules``) covers, per step: the idle
  the scheduler's own host work leaves between programs.  Idle inside a
  program execution is not counted;
- ``engine_idle``: for each innermost engine span (``engine.sync`` split by
  ``what``), the device idle between programs while it was the innermost
  open span, and the programs other than the engine's jitted steps that
  started then (the eager updates and reads the host issued).

Run on a trace kept by ``chipbench/run.py --trace 1 --trace-dir <dir>``::

    python3 -m chipbench.harness.engine_spans <dir or .xplane.pb>

It prints ``[engine_idle] {json}`` and ``[engine_spans] {json}``; the
latter also gives the harness's own labelled idle of its steps
(``admit``, ``admit.prefill``, ``decode``, ``harvest``) for comparison.
"""
from __future__ import annotations

import bisect
import collections
import json
import sys
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

from . import xtrace as X

#: the engine's jitted steps; any other program is one the host issued
#: eagerly (a slot-state write, a cursor read, a first-token sample)
ENGINE_PROGRAMS = frozenset({"jit_prefill", "jit_decode",
                             "jit_prefill_batch", "jit_verify"})
OUTSIDE = "outside engine spans"
#: the harness's gap labels that fall inside its ``cb.step`` span
STEP_GAPS = ("admit", "admit.prefill", "decode", "harvest")


class Cover:
    """How much of an interval the sorted, disjoint ``merged`` covers, by
    bisection (``xtrace.covered`` walks every interval)."""

    def __init__(self, merged: Sequence[X.Interval]):
        self.a = [a for a, _ in merged]
        self.b = [b for _, b in merged]
        self.cum = [0.0]
        for a, b in merged:
            self.cum.append(self.cum[-1] + b - a)

    def __call__(self, lo: float, hi: float) -> float:
        i = bisect.bisect_right(self.b, lo)
        j = bisect.bisect_left(self.a, hi)
        if i >= j:
            return 0.0
        return (self.cum[j] - self.cum[i] - max(0.0, lo - self.a[i])
                - max(0.0, self.b[j - 1] - hi))


def program_cover(red: X.Reduced) -> Cover:
    """Device 0's time inside some program execution."""
    return Cover(X.union((m.start, m.end) for m in red.devices[0].modules))


def _named(spans: Sequence[X.Event], name: str) -> List[X.Event]:
    return [s for s in spans if s.name == name]


def _starting_in(starts: Sequence[float], s: X.Event) -> int:
    return (bisect.bisect_left(starts, s.end)
            - bisect.bisect_left(starts, s.start))


def decode_steps(spans: Sequence[X.Event]) -> List[X.Event]:
    """The ``engine.step`` spans that dispatched a decode block."""
    dec = [d.start for d in _named(spans, "engine.decode")]
    return [s for s in _named(spans, "engine.step") if _starting_in(dec, s)]


def host_syncs_per_step(spans: Sequence[X.Event]) -> Optional[float]:
    steps = decode_steps(spans)
    if not steps:
        return None
    syncs = [s.start for s in _named(spans, "engine.sync")]
    return sum(_starting_in(syncs, s) for s in steps) / len(steps)


def sched_idle_ms_per_step(red: X.Reduced, spans: Sequence[X.Event]
                           ) -> Optional[float]:
    steps = _named(spans, "engine.step")
    if not steps:
        return None
    cover = program_cover(red)
    idle = 0.0
    for s in steps:
        hi = min(s.end, red.t1)
        idle += (hi - s.start) - cover(s.start, hi)
    return idle / len(steps) / 1e6


def label(span: X.Event) -> str:
    what = span.stats.get("what")
    return f"{span.name}:{what}" if what else span.name


def innermost(spans: Sequence[X.Event]
              ) -> List[Tuple[float, float, X.Event]]:
    """Cut the spans' union into ``(lo, hi, span)`` pieces, each with the
    innermost span open over it.  The engine's spans are opened on one
    thread, so they nest."""
    out: List[Tuple[float, float, X.Event]] = []
    stack: List[X.Event] = []
    t = 0.0

    def close_until(when):
        nonlocal t
        while stack and stack[-1].end <= when:
            top = stack.pop()
            if top.end > t:
                out.append((t, top.end, top))
            t = max(t, top.end)

    for s in sorted(spans, key=lambda e: (e.start, -e.end)):
        close_until(s.start)
        if stack and s.start > t:
            out.append((t, s.start, stack[-1]))
        stack.append(s)
        t = s.start
    close_until(float("inf"))
    return out


def engine_idle(red: X.Reduced, spans: Sequence[X.Event]
                ) -> Dict[str, Dict[str, object]]:
    """``{label: {"idle_s", "programs", "top"}}`` over the window: device
    idle between programs while each innermost engine span was open, the
    number of programs other than the engine's jitted steps that started
    then, and the three most frequent of those.  ``OUTSIDE`` holds the
    rest of the window (the harness's own time and its waits)."""
    cover = program_cover(red)
    idle: Dict[str, float] = collections.Counter()
    pieces = innermost(spans)
    for lo, hi, s in pieces:
        lo, hi = max(lo, red.t0), min(hi, red.t1)
        if hi > lo:
            idle[label(s)] += (hi - lo) - cover(lo, hi)
    idle[OUTSIDE] = ((red.t1 - red.t0) - cover(red.t0, red.t1)
                     - sum(idle.values()))
    starts = [lo for lo, _, _ in pieces]
    names: Dict[str, collections.Counter] = collections.defaultdict(
        collections.Counter)
    for m in red.devices[0].modules:
        prog = X.base_name(m.name)
        if not red.within(m) or prog in ENGINE_PROGRAMS:
            continue
        i = bisect.bisect_right(starts, m.start) - 1
        at = (label(pieces[i][2]) if i >= 0 and pieces[i][1] > m.start
              else OUTSIDE)
        names[at][prog] += 1
    return {k: {"idle_s": idle.get(k, 0.0) / 1e9,
                "programs": sum(names[k].values()),
                "top": names[k].most_common(3)}
            for k in sorted(set(idle) | set(names))}


def summary(red: X.Reduced, spans: Sequence[X.Event]) -> Dict[str, object]:
    """The two per-step numbers, their step counts and the parts they are
    made of, beside the harness's labelled idle of its steps."""
    steps, dec = _named(spans, "engine.step"), decode_steps(spans)
    syncs = [s for s in _named(spans, "engine.sync")
             if any(d.start <= s.start < d.end for d in dec)]
    whats = collections.Counter(s.stats.get("what") for s in syncs)
    eager = sum(1 for m in red.devices[0].modules if red.within(m)
                and X.base_name(m.name) not in ENGINE_PROGRAMS)
    labelled = X.label_gaps(red)
    gaps = dict(X.gap_totals(labelled, top=len(labelled)))
    idle = sched_idle_ms_per_step(red, spans)
    return {
        "steps": len(steps), "decode_steps": len(dec),
        "host_syncs_per_step": host_syncs_per_step(spans),
        "syncs_per_decode_step": {w: n / max(len(dec), 1)
                                  for w, n in sorted(whats.items())},
        "sched_idle_ms_per_step": idle,
        "sched_idle_s": None if idle is None else idle * len(steps) / 1e3,
        "harness_step_idle_s": sum(gaps.get(k, 0.0) for k in STEP_GAPS),
        "harness_gaps_s": gaps,
        "eager_programs": eager,
        "window_s": red.window_s,
    }


def _trace_file(path: Path) -> Path:
    if path.is_dir():
        return sorted(path.rglob("*.xplane.pb"))[-1]
    return path


def main(argv=None) -> int:
    args = sys.argv[1:] if argv is None else argv
    if len(args) != 1:
        print(__doc__, file=sys.stderr)
        return 2
    red = X.reduce(X.load(_trace_file(Path(args[0]))))
    spans = red.engine_spans
    for tag, facts in (("engine_idle", engine_idle(red, spans)),
                       ("engine_spans", summary(red, spans))):
        print(f"[{tag}] {json.dumps(facts, sort_keys=True)}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
