"""Reduce a profiler trace (``.xplane.pb``) to the numbers the benchmark
reads: device busy time, time per device operation and per program, the
device's idle gaps labelled by what the host was doing, and which
program execution served which engine step.

The trace is read with ``jax.profiler.ProfileData`` alone.  On a TPU the
device plane is ``/device:TPU:<n>``; its ``XLA Ops`` line holds one event
per operation run and its ``XLA Modules`` line one per program
execution (``jit_prefill``, ``jit_decode``, ...).  The host plane holds
the harness's own spans (``cb.step``, ``cb.prefill``, ``cb.decode``,
``cb.submit``, ``cb.wait``; see ``harness.loop``), on the same clock.
The program's own host spans (``engine.*``, opened by the serving
engine's scheduler; ``harness.engine_spans`` reads them) are kept apart,
in ``Reduced.engine_spans``.  The profiler runs from the window's opening
until after the drain; the span ``cb.window`` marks the measured window,
and every number read here is of that window.

A kernel is found by the start of its HLO instruction's name, which is
the jitted wrapper's (``%paged_decode.12 = bf16[...] custom-call(...)``),
so ``kernel_ops`` takes any kernel's name.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "cb."
ENGINE_PREFIX = "engine."
WINDOW_SPAN = "cb.window"

#: the paged kernels' instruction names, which ``tests/test_tpu_compile.py``
#: looks for in the engine's compiled steps; ``kernel_ops`` does not need
#: them
KERNEL_PATTERNS = {
    "paged_decode": "paged_decode",
    "paged_prefill": "paged_prefill",
}
#: ops that contain others in the ``XLA Ops`` line; their time is their
#: body's, so the per-op table leaves them out
CONTAINERS = ("while", "conditional", "call")

Interval = Tuple[float, float]


@dataclasses.dataclass
class Event:
    name: str
    start: float                # ns, on the trace's clock
    end: float
    stats: Dict[str, object]

    @property
    def dur(self) -> float:
        return self.end - self.start

    @property
    def op(self) -> str:
        """The HLO instruction's name: ``%fusion.3 = bf16[..] ...`` ->
        ``fusion.3``."""
        return self.name.split(" = ", 1)[0].lstrip("%")


@dataclasses.dataclass
class Device:
    name: str
    ops: List[Event]            # by start
    modules: List[Event]
    busy: List[Interval]        # union of op intervals, sorted, disjoint

    def __post_init__(self):
        self.starts = [e.start for e in self.ops]


@dataclasses.dataclass
class Reduced:
    t0: float                   # measured window, ns
    t1: float
    devices: List[Device]
    spans: List[Event]          # harness host spans, by start
    engine_spans: List[Event] = dataclasses.field(default_factory=list)
                                # the program's, that start in the window

    def within(self, e: Event) -> bool:
        """Whether ``e`` started inside the window."""
        return self.t0 <= e.start < self.t1

    @property
    def window_s(self) -> float:
        return (self.t1 - self.t0) / 1e9

    @property
    def busy_s(self) -> float:
        """Busy seconds, averaged over the devices."""
        return sum(covered(d.busy, self.t0, self.t1)
                   for d in self.devices) / len(self.devices) / 1e9


def _event(ev, stats: bool = True) -> Event:
    start = float(ev.start_ns)
    return Event(ev.name, start, start + float(ev.duration_ns),
                 dict(ev.stats) if stats else {})


def union(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def covered(merged: Sequence[Interval], lo: float, hi: float) -> float:
    """Length of ``[lo, hi)`` that the sorted disjoint ``merged`` covers."""
    return sum(max(0.0, min(b, hi) - max(a, lo)) for a, b in merged)


def gaps(merged: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """The parts of ``[lo, hi)`` that ``merged`` leaves uncovered."""
    out, t = [], lo
    for a, b in merged:
        if b <= lo:
            continue
        if a >= hi:
            break
        if a > t:
            out.append((t, min(a, hi)))
        t = max(t, b)
    if t < hi:
        out.append((t, hi))
    return out


def load(path_or_bytes):
    from jax.profiler import ProfileData
    if isinstance(path_or_bytes, (bytes, bytearray)):
        return ProfileData.from_serialized_xspace(bytes(path_or_bytes))
    return ProfileData.from_file(str(path_or_bytes))


def reduce(pd) -> Reduced:
    devices, spans, engine, window = [], [], [], None
    lo, hi = float("inf"), float("-inf")
    for plane in pd.planes:
        stats = dict(plane.stats)
        if "profile_start_time" in stats and "profile_stop_time" in stats:
            window = float(stats["profile_stop_time"]) - float(
                stats["profile_start_time"])
        if DEVICE_PLANE.match(plane.name):
            ops, mods = [], []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops = [_event(e, stats=False) for e in line.events]
                elif line.name == MODULES_LINE:
                    mods = [_event(e) for e in line.events]
            ops.sort(key=lambda e: e.start)
            mods.sort(key=lambda e: e.start)
            devices.append(Device(plane.name, ops, mods,
                                  union((e.start, e.end) for e in ops)))
            for e in ops:
                lo, hi = min(lo, e.start), max(hi, e.end)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        e = _event(ev)
                        spans.append(e)
                        lo, hi = min(lo, e.start), max(hi, e.end)
                    elif ev.name.startswith(ENGINE_PREFIX):
                        engine.append(_event(ev))
    if not devices:
        raise ValueError("trace has no TPU device plane")
    spans.sort(key=lambda e: e.start)
    marked = [s for s in spans if s.name == WINDOW_SPAN]
    if marked:
        t0, t1 = marked[0].start, marked[0].end
    else:
        t0 = 0.0 if window is not None else lo
        t1 = window if window is not None else hi
    engine = sorted((e for e in engine if t0 <= e.start < t1),
                    key=lambda e: e.start)
    return Reduced(t0, t1, devices, spans, engine)


def base_name(name: str) -> str:
    """``jit_decode(12)`` -> ``jit_decode``."""
    return name.split("(", 1)[0]


def op_time(red: Reduced, top: int = 10) -> List[Tuple[str, float]]:
    """Device seconds per operation in the window, most first (device 0),
    leaving out the loops and calls that contain other operations."""
    tot: Dict[str, float] = collections.Counter()
    for e in red.devices[0].ops:
        if red.within(e) and e.op.split(".", 1)[0] not in CONTAINERS:
            tot[e.op] += e.dur / 1e9
    return sorted(tot.items(), key=lambda kv: -kv[1])[:top]


def spans_named(red: Reduced, name: str) -> List[Event]:
    return [s for s in red.spans if s.name == name]


def modules_named(red: Reduced, module: str) -> List[Event]:
    """Executions of program ``module`` on device 0 in the window."""
    return [m for m in red.devices[0].modules
            if base_name(m.name) == module and red.within(m)]


def match_modules(red: Reduced, module: str, span: str
                  ) -> List[Tuple[Event, int]]:
    """Pair each execution of program ``module`` on device 0 with the
    host span ``span`` that dispatched it, in order, and return
    ``(module_event, ev)`` for those in the window, where ``ev`` is the
    span's engine trace index.  The profiler starts and stops when
    nothing is in flight, so every traced dispatch ran inside the trace;
    where the counts still differ the earliest unpaired ones are
    dropped."""
    mods = [m for m in red.devices[0].modules if base_name(m.name) == module]
    sps = spans_named(red, span)
    n = min(len(mods), len(sps))
    mods, sps = mods[len(mods) - n:], sps[len(sps) - n:]
    return [(m, int(s.stats["ev"])) for m, s in zip(mods, sps)
            if red.within(m)]


def kernel_ops(red: Reduced, kernel: str, lo: float, hi: float
               ) -> List[Event]:
    """Device-0 events of ``kernel`` (those whose HLO instruction's name
    starts with it) that start within ``[lo, hi)``."""
    dev = red.devices[0]
    ops = dev.ops
    i = bisect.bisect_left(dev.starts, lo)
    out = []
    while i < len(ops) and ops[i].start < hi:
        if ops[i].op.startswith(kernel):
            out.append(ops[i])
        i += 1
    return out


def label_gaps(red: Reduced, within: Optional[Sequence[Interval]] = None
               ) -> List[Tuple[str, float, float]]:
    """Each idle gap of device 0 while the harness ran in the window
    (from its first span to its last inside it; clipped to ``within``
    when given), as ``(label, start, end)``.  The label is what
    the host was doing at the gap's middle: ``admit.prefill`` or
    ``decode`` (dispatching that step), ``admit`` or ``harvest`` (the
    engine's own host work before or after its decode dispatch),
    ``submit``, ``waiting for arrivals``, or ``harness``."""
    if within is None:
        inner = [s for s in red.spans if s.name != WINDOW_SPAN]
        within = [(max(red.t0, min(s.start for s in inner)),
                   min(red.t1, max(s.end for s in inner)))] if inner else []
    windows = within
    steps = spans_named(red, "cb.step")
    step_starts = [s.start for s in steps]
    decodes = spans_named(red, "cb.decode")
    dec_starts = [d.start for d in decodes]
    others = [s for s in red.spans if s.name in
              ("cb.prefill", "cb.decode", "cb.submit", "cb.wait")]
    names = {"cb.prefill": "admit.prefill", "cb.decode": "decode",
             "cb.submit": "submit", "cb.wait": "waiting for arrivals"}
    o_starts = [s.start for s in others]
    out = []
    for lo, hi in windows:
        for a, b in gaps(red.devices[0].busy, lo, hi):
            t = (a + b) / 2
            label = "harness"
            i = bisect.bisect_right(o_starts, t) - 1
            if i >= 0 and others[i].end >= t:
                label = names[others[i].name]
            else:
                j = bisect.bisect_right(step_starts, t) - 1
                if j >= 0 and steps[j].end >= t:
                    s = steps[j]
                    k = bisect.bisect_left(dec_starts, s.start)
                    d = decodes[k] if k < len(decodes) else None
                    label = ("harvest" if d is not None and d.end <= s.end
                             and t >= d.end else "admit")
            out.append((label, a, b))
    return out


def gap_totals(labelled: Iterable[Tuple[str, float, float]], top: int = 10
               ) -> List[Tuple[str, float]]:
    tot: Dict[str, float] = collections.Counter()
    for label, a, b in labelled:
        tot[label] += (b - a) / 1e9
    return sorted(tot.items(), key=lambda kv: -kv[1])[:top]


def in_flight(red: Reduced) -> List[Interval]:
    """The window less the harness's waits: the loop waits only when no
    request is queued or running."""
    return gaps(union((s.start, s.end) for s in spans_named(red, "cb.wait")),
                red.t0, red.t1)
