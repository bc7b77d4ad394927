"""The trace reduction against a small hand-made trace whose numbers are
worked out by hand below (times in ns; the traced window is 100 us)."""
from __future__ import annotations

import pytest

from chipbench.arch import dense
from chipbench.harness import xtrace as X
from chipbench.harness.loop import Served, StepRec
from chipbench.harness.record import Run
from chipbench.metrics import (device_idle_share, decode_step_ms,
                               paged_decode_roofline, paged_prefill_roofline,
                               prefill_chunk_ms)

# device 0: (line, name, start, end, long_name)
DEVICE = [
    ("XLA Ops", "fusion.1", 0, 10_000, "jit(decode)/dot_general"),
    ("XLA Ops", "%paged_decode.7 = bf16[4] custom-call(%fusion.1)", 10_000,
     30_000, ""),
    ("XLA Ops", "%fusion.2 = bf16[4] fusion(%paged_decode.7)", 25_000, 40_000,
     ""),
    ("XLA Ops", "fusion.4", 50_000, 52_000, "jit(scatter)/scatter"),
    ("XLA Ops", "%paged_prefill.9 = bf16[4] custom-call()", 60_000, 70_000,
     ""),
    ("XLA Ops", "%while.5 = (s32[]) while()", 60_000, 70_000, ""),
    ("XLA Ops", "fusion.3", 90_000, 95_000, "jit(_where)/select"),
    ("XLA Modules", "jit_decode(11)", 0, 40_000, ""),
    ("XLA Modules", "jit_prefill(12)", 60_000, 70_000, ""),
]
# host spans of the harness: (name, start, end, ev)
HOST = [
    ("cb.step", 0, 50_000, None),
    ("cb.decode", 1_000, 2_000, 4),
    ("cb.step", 50_000, 75_000, None),
    ("cb.prefill", 55_000, 57_000, 2),
    ("cb.wait", 72_000, 88_000, None),
]


def _plane(pid, name, lines, stats=""):
    names = sorted({e[1] for ln in lines.values() for e in ln})
    ids = {n: i + 1 for i, n in enumerate(names)}
    out = [f'planes {{ id: {pid} name: "{name}"']
    for li, (lname, evs) in enumerate(lines.items()):
        out.append(f'  lines {{ id: {li + 1} name: "{lname}" timestamp_ns: 0')
        for e in evs:
            _, n, a, b, stat = e
            st = ""
            if isinstance(stat, str) and stat:
                st = f'stats {{ metadata_id: 1 str_value: "{stat}" }}'
            elif isinstance(stat, int):
                st = f'stats {{ metadata_id: 1 int64_value: {stat} }}'
            out.append(f"    events {{ metadata_id: {ids[n]} offset_ps: "
                       f"{a * 1000} duration_ps: {(b - a) * 1000} {st} }}")
        out.append("  }")
    for n, i in ids.items():
        out.append(f'  event_metadata {{ key: {i} value {{ id: {i} '
                   f'name: "{n}" }} }}')
    out.append(f'  stat_metadata {{ key: 1 value {{ id: 1 name: '
               f'"{"ev" if name.startswith("/host") else "long_name"}" }} }}')
    out.append(stats + " }")
    return "\n".join(out)


def trace_text(spans=HOST):
    dev = {}
    for e in DEVICE:
        dev.setdefault(e[0], []).append(e)
    host = {"python": [("python", n, a, b, ev) for n, a, b, ev in spans]}
    env = ('planes { id: 3 name: "Task Environment" '
           'stats { metadata_id: 1 uint64_value: 1000000000 } '
           'stats { metadata_id: 2 uint64_value: 1000100000 } '
           'stat_metadata { key: 1 value { id: 1 name: "profile_start_time" } } '
           'stat_metadata { key: 2 value { id: 2 name: "profile_stop_time" } } }')
    return "\n".join([_plane(1, "/device:TPU:0", dev),
                      _plane(2, "/host:CPU", host), env])


@pytest.fixture(scope="module")
def red():
    from jax.profiler import ProfileData
    return X.reduce(ProfileData.from_text_proto(trace_text()))


def test_busy_and_window(red):
    # ops cover [0, 40000) (two overlap), [50000, 52000), [60000, 70000)
    # and [90000, 95000)
    assert red.window_s == pytest.approx(100e-6)
    assert red.busy_s == pytest.approx(57e-6)
    assert red.devices[0].busy == [(0, 40_000), (50_000, 52_000),
                                   (60_000, 70_000), (90_000, 95_000)]


def test_op_time_most_first(red):
    top = X.op_time(red)
    assert top[0] == ("paged_decode.7", pytest.approx(20e-6))
    assert dict(top)["fusion.3"] == pytest.approx(5e-6)
    assert "while.5" not in dict(top)           # a loop holds other ops


def test_gaps_labelled_by_host_span(red):
    got = X.label_gaps(red)
    assert [(lbl, a, b) for lbl, a, b in got] == [
        ("harvest", 40_000, 50_000),              # in a step, after decode
        ("admit.prefill", 52_000, 60_000),        # midpoint in cb.prefill
        ("waiting for arrivals", 70_000, 88_000)]   # to the last span's end
    tot = dict(X.gap_totals(got))
    assert tot["waiting for arrivals"] == pytest.approx(18e-6)


def test_modules_pair_with_their_dispatch(red):
    (m, ev), = X.match_modules(red, "jit_decode", "cb.decode")
    assert (m.start, m.end, ev) == (0, 40_000, 4)
    (m, ev), = X.match_modules(red, "jit_prefill", "cb.prefill")
    assert (m.start, ev) == (60_000, 2)
    ops = X.kernel_ops(red, "paged_decode", 0, 40_000)
    assert [o.op for o in ops] == ["paged_decode.7"]   # not its consumer


class _Ev:
    def __init__(self, kind, **kw):
        self.kind, self.slots, self.past_len, self.chunk = kind, (), 0, 0
        self.__dict__.update(kw)


ARCH = {"n_layers": 2, "n_kv_heads": 2, "n_heads": 4, "head_dim": 16,
        "d_model": 64, "d_ff": 128, "vocab_size": 512}
PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e9}


def _run(red):
    events = [_Ev("engine"), _Ev("x"),
              _Ev("prefill_chunk", past_len=256, chunk=100),
              _Ev("x"),
              _Ev("decode_block", slots=((1, 99, 8), (2, 9, 3)))]
    served = Served(recs=[], steps=[StepRec(0, 1, 0, 0)], t_open=0,
                    t_close=1, tokens_in_window=0, lateness=[])
    return Run(arch=ARCH, architecture=dense,
               engine={"decode_block": 8, "kv_dtype": "bf16"},
               peaks=PEAKS, served=served, events=events, trace=red)


def test_kernel_rooflines_by_hand(red):
    run = _run(red)
    # decode: Hk=2, G=2, d=16, bf16.  Step t: slot 1 attends 100+t keys
    # (t < 8), slot 2 attends 10+t (t < 3).  Per call, bytes = K and V of
    # every key (2*2*16*2 = 128 B/key) + q in and out (2*slots*2*2*16*2 =
    # 256 B/slot); flops = 4*2*2*16 = 256/key.  Memory bound at these
    # peaks (256 flop/key at 1e12 vs 128 B/key at 1e9).
    least = 0.0
    for t in range(8):
        keys = 100 + t + (10 + t if t < 3 else 0)
        slots = 2 if t < 3 else 1
        least += (128 * keys + 256 * slots) / 1e9
    least *= 2                                   # two layers
    assert paged_decode_roofline.read(run) == pytest.approx(
        100 * least / 20e-6)
    # prefill: 100 rows from 256: bytes 128*(256+100) + 256*100
    least = 2 * (128 * 356 + 256 * 100) / 1e9
    assert paged_prefill_roofline.read(run) == pytest.approx(
        100 * least / 10e-6)


def test_device_metrics_by_hand(red):
    run = _run(red)
    # in flight: the window less the wait [72000, 88000): 84000 ns, of
    # which the device is busy 40000 + 2000 + 10000 + 5000
    assert device_idle_share.read(run) == pytest.approx(
        100 * (1 - 57_000 / 84_000))
    assert prefill_chunk_ms.read(run) == pytest.approx(0.010)
    assert decode_step_ms.read(run) == pytest.approx(0.040 / 8)


def test_no_trace_reads_nothing(red):
    run = _run(None)
    for mod in (device_idle_share, decode_step_ms, paged_decode_roofline,
                paged_prefill_roofline, prefill_chunk_ms):
        assert mod.read(run) is None


def test_window_span_clips_every_reading():
    # the harness marks its measured window [50000, 100000) with a span;
    # the decode program and its kernel ran before it
    from jax.profiler import ProfileData
    red = X.reduce(ProfileData.from_text_proto(trace_text(
        HOST + [("cb.window", 50_000, 100_000, None)])))
    assert (red.t0, red.t1) == (50_000, 100_000)
    assert red.busy_s == pytest.approx(17e-6)
    assert "paged_decode.7" not in dict(X.op_time(red))
    assert X.match_modules(red, "jit_decode", "cb.decode") == []
    assert [ev for _, ev in X.match_modules(
        red, "jit_prefill", "cb.prefill")] == [2]
    assert [m.start for m in X.modules_named(red, "jit_prefill")] == [60_000]
    assert X.modules_named(red, "jit_decode") == []
    run = _run(red)
    assert decode_step_ms.read(run) is None
    assert prefill_chunk_ms.read(run) == pytest.approx(0.010)
    # in flight: the window less the wait's part in it [72000, 88000)
    assert device_idle_share.read(run) == pytest.approx(
        100 * (1 - 17_000 / 34_000))
    assert [(lbl, a, b) for lbl, a, b in X.label_gaps(red)] == [
        ("admit.prefill", 52_000, 60_000),
        ("waiting for arrivals", 70_000, 88_000)]


def test_every_metric_has_a_reader_and_moves_what_its_cells_report():
    import json

    from chipbench.harness import spec
    bench = json.loads((spec.ROOT / "BENCHMARK.json").read_text())
    for w in bench["workloads"]:
        cell = spec.load_cell(w["name"])
        reported = {m["name"] for m in cell.end_to_end}
        assert {"setup_s"} < reported and cell.per_layer
        for m in cell.per_layer:
            assert callable(spec.reader(m["name"]))
            assert m["moves"] in reported, (w["name"], m["name"])


def test_kernel_ops_takes_a_kernel_in_no_table():
    from jax.profiler import ProfileData
    ops = {"XLA Ops": [
        ("XLA Ops", "%mla_decode.3 = bf16[4] custom-call()", 0, 5_000, ""),
        ("XLA Ops", "%fusion.1 = bf16[4] fusion(%mla_decode.3)", 5_000,
         6_000, ""),
        ("XLA Ops", "%mla_decode.4 = bf16[4] custom-call()", 7_000, 9_000,
         "")]}
    env = trace_text().split("\n")[-1]
    red = X.reduce(ProfileData.from_text_proto(
        "\n".join([_plane(1, "/device:TPU:0", ops), env])))
    assert "mla_decode" not in X.KERNEL_PATTERNS
    assert [o.op for o in X.kernel_ops(red, "mla_decode", 0, 10_000)] == [
        "mla_decode.3", "mla_decode.4"]
    assert [o.op for o in X.kernel_ops(red, "mla_decode", 6_000, 10_000)] \
        == ["mla_decode.4"]


def test_reduce_keeps_the_programs_spans_apart():
    """``engine.*`` spans that start in the window are kept, by start and
    with their arguments, in ``engine_spans``; the harness's spans and
    every reading taken from them are what they were without them."""
    from jax.profiler import ProfileData
    from chipbench.metrics import step_mfu
    engine = [("engine.decode", 1_000, 2_000, 4),
              ("engine.step", 0, 49_000, None),
              ("engine.sync", 99_000, 101_000, None),
              ("engine.sync", 100_000, 101_000, None)]      # after the window
    without, with_ = [X.reduce(ProfileData.from_text_proto(trace_text(s)))
                      for s in (HOST, HOST + engine)]
    assert without.engine_spans == []
    assert [(s.name, s.start, s.end) for s in with_.engine_spans] == [
        ("engine.step", 0, 49_000), ("engine.decode", 1_000, 2_000),
        ("engine.sync", 99_000, 101_000)]
    assert with_.engine_spans[1].stats["ev"] == 4
    assert [(s.name, s.start, s.end, s.stats) for s in with_.spans] == [
        (s.name, s.start, s.end, s.stats) for s in without.spans]
    assert (with_.t0, with_.t1, with_.busy_s) == (
        without.t0, without.t1, without.busy_s)
    for mod in (device_idle_share, decode_step_ms, paged_decode_roofline,
                paged_prefill_roofline, prefill_chunk_ms, step_mfu):
        assert mod.read(_run(with_)) == mod.read(_run(without))
