"""The engine's spans read against a small hand-made trace whose numbers
are worked out by hand below (times in ns; the traced window is 100 us).

Device 0 runs ``jit_decode`` over [10000, 30000) with its two operations
leaving [18000, 22000) idle inside the program, two eager programs
(``jit_convert_element_type`` at 35000, ``jit_dynamic_slice`` at 40000,
1000 each) and ``jit_prefill`` over [50000, 60000).  The host runs two
engine steps: the first reads a cursor, dispatches the decode block,
waits for it and harvests, freeing a slot (which issues both eager
programs); the second admits a request and waits for its first token.
"""
from __future__ import annotations

import pytest

from chipbench.harness import engine_spans as E
from chipbench.harness import xtrace as X
from chipbench.tests import test_xtrace as T
from chipbench.metrics import (device_idle_share, decode_step_ms,
                               host_syncs_per_step, paged_decode_roofline,
                               paged_prefill_roofline, prefill_chunk_ms,
                               sched_idle_ms_per_step)

# device 0: (line, name, start, end, long_name)
DEVICE = [
    ("XLA Ops", "fusion.1", 10_000, 18_000, ""),
    ("XLA Ops", "%paged_decode.7 = bf16[4] custom-call()", 22_000, 30_000,
     ""),
    ("XLA Ops", "convert.2", 35_000, 36_000, ""),
    ("XLA Ops", "dynamic-slice.3", 40_000, 41_000, ""),
    ("XLA Ops", "%paged_prefill.9 = bf16[4] custom-call()", 50_000, 60_000,
     ""),
    ("XLA Modules", "jit_decode(11)", 10_000, 30_000, ""),
    ("XLA Modules", "jit_convert_element_type(3)", 35_000, 36_000, ""),
    ("XLA Modules", "jit_dynamic_slice(4)", 40_000, 41_000, ""),
    ("XLA Modules", "jit_prefill(12)", 50_000, 60_000, ""),
]
# host: (name, start, end, stats)
ENGINE = [
    ("engine.step", 1_000, 44_000, {"step": 0, "ev": 1}),
    ("engine.sync", 2_000, 4_000, {"what": "cursor"}),
    ("engine.decode", 5_000, 8_000, {"ev": 1}),
    ("engine.sync", 8_000, 31_000, {"what": "ready"}),
    ("engine.sync", 31_000, 32_000, {"what": "tokens"}),
    ("engine.sync", 32_000, 33_000, {"what": "produced"}),
    ("engine.harvest", 33_000, 43_000, {}),
    ("engine.free", 34_000, 42_000, {"slot": 0}),
    ("engine.step", 47_000, 61_000, {"step": 1, "ev": 2}),
    ("engine.admit", 48_000, 60_500, {"rid": 3, "slot": 0}),
    ("engine.prefill", 49_000, 50_000, {"ev": 2}),
    ("engine.sync", 50_000, 60_400, {"what": "first_token"}),
]
HARNESS = [("cb.step", 0, 45_000, {}), ("cb.decode", 5_000, 8_000, {"ev": 1}),
           ("cb.step", 46_000, 62_000, {}),
           ("cb.prefill", 49_000, 50_000, {"ev": 2})]


def _host_plane(spans):
    names = sorted({s[0] for s in spans})
    stats = sorted({k for s in spans for k in s[3]})
    nid = {n: i + 1 for i, n in enumerate(names)}
    sid = {n: i + 1 for i, n in enumerate(stats)}
    out = ['planes { id: 2 name: "/host:CPU"',
           '  lines { id: 1 name: "python" timestamp_ns: 0']
    for n, a, b, st in spans:
        body = " ".join(
            f'stats {{ metadata_id: {sid[k]} '
            + (f'str_value: "{v}"' if isinstance(v, str)
               else f"int64_value: {v}") + " }"
            for k, v in st.items())
        out.append(f"    events {{ metadata_id: {nid[n]} offset_ps: "
                   f"{a * 1000} duration_ps: {(b - a) * 1000} {body} }}")
    out.append("  }")
    out += [f'  event_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}'
            for n, i in nid.items()]
    out += [f'  stat_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}'
            for n, i in sid.items()]
    return "\n".join(out + ["}"])


def _profile(device, host):
    from jax.profiler import ProfileData
    dev = {}
    for e in device:
        dev.setdefault(e[0], []).append(e)
    env = T.trace_text().split("\n")[-1]          # the window's bounds
    return ProfileData.from_text_proto("\n".join(
        [T._plane(1, "/device:TPU:0", dev), _host_plane(host), env]))


@pytest.fixture(scope="module")
def read():
    pd = _profile(DEVICE, HARNESS + ENGINE)
    red = X.reduce(pd)
    return red, red.engine_spans


def test_spans_read_in_the_window(read):
    red, spans = read
    assert (red.t0, red.t1) == (0, 100_000)
    assert [s.name for s in spans] == [s[0] for s in ENGINE]
    assert spans[1].stats["what"] == "cursor"
    assert [s.name for s in red.spans] == [s[0] for s in sorted(
        HARNESS, key=lambda s: s[1])]             # xtrace keeps only cb.*


def test_syncs_count_only_in_steps_that_decode(read):
    # step 0: cursor, ready, tokens, produced; step 1 (its first-token
    # sync) dispatched no decode block
    _, spans = read
    assert [s.start for s in E.decode_steps(spans)] == [1_000]
    assert E.host_syncs_per_step(spans) == 4.0


def test_sched_idle_counts_gaps_between_programs_only(read):
    # step 0 [1000, 44000): 43000 less the programs' 20000 + 1000 + 1000;
    # the idle [18000, 22000) inside jit_decode does not count.
    # step 1 [47000, 61000): 14000 less jit_prefill's 10000
    red, spans = read
    assert E.sched_idle_ms_per_step(red, spans) == pytest.approx(
        (21_000 + 4_000) / 2 / 1e6)


def test_readers_take_the_engine_spans_from_the_reduced_trace(read):
    red, _ = read
    run = T._run(red)
    assert host_syncs_per_step.read(run) == 4.0
    assert sched_idle_ms_per_step.read(run) == pytest.approx(
        (21_000 + 4_000) / 2 / 1e6)
    for reader in (host_syncs_per_step, sched_idle_ms_per_step):
        assert reader.read(T._run(None)) is None
    # a trace without the engine's spans has nothing for them to read
    bare = X.reduce(_profile(DEVICE, HARNESS))
    assert host_syncs_per_step.read(T._run(bare)) is None
    assert sched_idle_ms_per_step.read(T._run(bare)) is None


def test_engine_idle_by_innermost_span(read):
    red, spans = read
    got = E.engine_idle(red, spans)
    idle = {k: v["idle_s"] * 1e9 for k, v in got.items()}
    assert idle == pytest.approx({
        "engine.step": 1_000 + 1_000 + 1_000 + 1_000 + 500,
        "engine.sync:cursor": 2_000, "engine.decode": 3_000,
        "engine.sync:ready": 3_000, "engine.sync:tokens": 1_000,
        "engine.sync:produced": 1_000, "engine.harvest": 2_000,
        "engine.free": 6_000, "engine.admit": 1_100,
        "engine.prefill": 1_000, "engine.sync:first_token": 400,
        # the window's idle between programs (100000 - 32000) less the
        # steps' 25000
        E.OUTSIDE: 68_000 - 25_000})
    assert got["engine.free"]["programs"] == 2
    assert dict(got["engine.free"]["top"]) == {
        "jit_convert_element_type": 1, "jit_dynamic_slice": 1}
    assert sum(v["programs"] for v in got.values()) == 2


def test_summary_sets_engine_idle_beside_the_harness_labels(read):
    red, spans = read
    s = E.summary(red, spans)
    assert (s["steps"], s["decode_steps"], s["eager_programs"]) == (2, 1, 2)
    assert s["syncs_per_decode_step"] == {
        "cursor": 1.0, "produced": 1.0, "ready": 1.0, "tokens": 1.0}
    assert s["sched_idle_s"] == pytest.approx(25_000 / 1e9)
    # the harness labels the gaps between operations by the host span at
    # their middle: [0, 10000) decode (cb.decode opens at its middle),
    # [18000, 22000) (inside jit_decode), [30000, 35000) and
    # [36000, 40000) harvest, [41000, 50000) between its steps, and
    # [60000, 62000) admit
    assert s["harness_gaps_s"] == pytest.approx({
        "decode": 10e-6, "harvest": 13e-6, "harness": 9e-6, "admit": 2e-6})
    assert s["harness_step_idle_s"] == pytest.approx(25e-6)


def test_cover_by_bisection_agrees_with_xtrace():
    merged = [(0, 10), (20, 30), (35, 36), (50, 80)]
    cover = E.Cover(merged)
    for lo in range(-5, 90, 3):
        for hi in range(lo, 95, 7):
            assert cover(lo, hi) == pytest.approx(X.covered(merged, lo, hi))


def test_innermost_pieces_tile_the_spans():
    red = X.reduce(_profile(DEVICE, ENGINE))
    spans = red.engine_spans
    pieces = E.innermost(spans)
    assert all(a < b for a, b, _ in pieces)
    assert all(p[1] <= q[0] for p, q in zip(pieces, pieces[1:]))
    covered = sum(b - a for a, b, _ in pieces)
    assert covered == (44_000 - 1_000) + (61_000 - 47_000)


def test_engine_spans_leave_every_existing_reading_as_it_was():
    """The same trace read with and without the engine's spans gives the
    same host spans, gap labels, modules and readings."""
    from jax.profiler import ProfileData
    engine = [("engine.step", 0, 49_000, 1), ("engine.decode", 1_000,
                                               2_000, 4),
              ("engine.sync", 2_000, 41_000, None),
              ("engine.harvest", 41_000, 48_000, None),
              ("engine.step", 50_000, 74_000, 2),
              ("engine.prefill", 55_000, 57_000, 2)]
    reds = [X.reduce(ProfileData.from_text_proto(T.trace_text(spans)))
            for spans in (T.HOST, T.HOST + engine)]
    without, with_ = reds
    assert [(s.name, s.start, s.end) for s in with_.spans] == [
        (s.name, s.start, s.end) for s in without.spans]
    assert (with_.t0, with_.t1, with_.busy_s) == (
        without.t0, without.t1, without.busy_s)
    assert X.label_gaps(with_) == X.label_gaps(without)
    assert X.op_time(with_) == X.op_time(without)
    assert X.in_flight(with_) == X.in_flight(without)
    for mod, span in (("jit_decode", "cb.decode"),
                      ("jit_prefill", "cb.prefill")):
        assert ([(m.start, ev) for m, ev in X.match_modules(with_, mod, span)]
                == [(m.start, ev) for m, ev in
                    X.match_modules(without, mod, span)])
    for reader in (device_idle_share, decode_step_ms, paged_decode_roofline,
                   paged_prefill_roofline, prefill_chunk_ms):
        assert reader.read(T._run(with_)) == reader.read(T._run(without))
