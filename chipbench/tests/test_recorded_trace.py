"""The trace reduction on one engine step recorded on a v5e chip.

``data/qwen_step_trace.txtpb.gz`` is an excerpt of a profiler trace of
the qwen2-7b-l20 cell (20 layers, 16-token KV blocks) taken on one TPU
v5e: one ``cb.step`` span in which the engine admitted prompt chunks
(39 ``jit_prefill`` executions) and then ran one fused decode block
(one ``jit_decode`` of 8 token steps), with the device's ``XLA Ops`` and
``XLA Modules`` lines and the harness's host spans (operation names cut
to their HLO instruction name)."""
from __future__ import annotations

import gzip
from pathlib import Path

import pytest

from chipbench.harness import xtrace as X

DATA = Path(__file__).resolve().parent / "data" / "qwen_step_trace.txtpb.gz"


@pytest.fixture(scope="module")
def red():
    from jax.profiler import ProfileData
    with gzip.open(DATA, "rt") as f:
        return X.reduce(ProfileData.from_text_proto(f.read()))


def test_each_program_pairs_with_its_dispatch(red):
    dec = X.match_modules(red, "jit_decode", "cb.decode")
    pre = X.match_modules(red, "jit_prefill", "cb.prefill")
    assert [ev for _, ev in dec] == [99]
    assert [ev for _, ev in pre] == list(range(60, 99))
    # one paged kernel call per layer, per fused token step for decode
    (m, _), = dec
    assert len(X.kernel_ops(red, "paged_decode", m.start, m.end)) == 8 * 20
    assert len(X.kernel_ops(red, "paged_prefill", m.start, m.end)) == 0
    for m, _ in pre:
        assert len(X.kernel_ops(red, "paged_prefill", m.start, m.end)) == 20


def test_busy_by_a_plain_sweep(red):
    # the same union of op intervals, counted another way: sweep the
    # sorted starts and ends with a depth counter
    marks = sorted([(e.start, 1) for e in red.devices[0].ops]
                   + [(e.end, -1) for e in red.devices[0].ops],
                   key=lambda m: (m[0], -m[1]))
    depth, busy, since = 0, 0.0, None
    for t, d in marks:
        if depth == 0 and d == 1:
            since = t
        depth += d
        if depth == 0:
            busy += t - since
    assert red.busy_s == pytest.approx(busy / 1e9, rel=1e-9)
    assert 0 < red.busy_s <= red.window_s


def test_kernel_time_within_its_programs(red):
    (m, _), = X.match_modules(red, "jit_decode", "cb.decode")
    k = sum(e.dur for e in X.kernel_ops(red, "paged_decode", m.start, m.end))
    assert 0 < k < m.dur
    top = dict(X.op_time(red))
    assert top["paged_prefill.11"] > top["paged_decode.12"] > 0


def test_gaps_fall_in_host_spans(red):
    got = X.label_gaps(red)
    assert got and {lbl for lbl, _, _ in got} <= {
        "admit", "admit.prefill", "decode", "harvest", "submit",
        "waiting for arrivals", "harness"}
    idle = sum(b - a for _, a, b in got) / 1e9
    assert idle <= red.window_s - red.busy_s + 1e-9
