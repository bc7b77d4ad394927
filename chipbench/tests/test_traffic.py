"""The generator offers every seed the same work in another order."""
from __future__ import annotations

import json
from collections import Counter

import numpy as np
import pytest

from chipbench.harness import spec, traffic as T
from chipbench.tests import tiny

MIXES = {n: json.loads((spec.BENCH_DIR / "traffic" / f"{n}.json").read_text())
         for n in ("chat-poisson", "longctx-closed8")}
MIXES["tiny-sessions"] = json.loads(
    (tiny.DATA / "tiny-sessions.json").read_text())
BIG = 2**31 + 987_654_321


def _sizes(tr):
    return Counter((len(r.prompt), r.max_new, r.turn, r.adapter_id)
                   for r in tr.requests)


def _gaps(mix, tr, window_s=45.0, drain_s=60.0):
    """Each span's gaps from one due time to the next or to the span's
    end, sorted: the multiset of gaps the seed puts in order."""
    if mix["arrival"]["kind"] == "closed":
        return []
    due = np.array([s.due_s for s in tr.sessions])
    out, t0 = [], 0.0
    for span in (mix.get("warm_s", 0.0), window_s, drain_s):
        d = due[(due >= t0) & (due < t0 + span)]
        out += np.diff(np.append(d, t0 + span)).tolist() if len(d) else []
        t0 += span
    return sorted(out)


@pytest.mark.parametrize("mix", sorted(MIXES))
def test_same_work_other_order(mix):
    a = T.generate(MIXES[mix], 3, 45.0, 1000, 60.0)
    b = T.generate(MIXES[mix], BIG, 45.0, 1000, 60.0)
    assert _sizes(a) == _sizes(b)
    assert _gaps(MIXES[mix], a) == pytest.approx(_gaps(MIXES[mix], b))
    assert [len(r.prompt) for r in a.requests] != [
        len(r.prompt) for r in b.requests]
    again = T.generate(MIXES[mix], BIG, 45.0, 1000, 60.0)
    assert all(np.array_equal(x.prompt, y.prompt)
               for x, y in zip(b.requests, again.requests))
    assert [s.due_s for s in b.sessions] == [s.due_s for s in again.sessions]


@pytest.mark.parametrize("mix", ["chat-poisson", "longctx-closed8"])
def test_lengths_within_bounds(mix):
    m = MIXES[mix]
    tr = T.generate(m, 5, 45.0, 1000, 60.0)
    p = [len(r.prompt) for r in tr.requests]
    o = [r.max_new for r in tr.requests]
    assert m["prompt"]["min"] <= min(p) and max(p) <= m["prompt"]["max"]
    assert m["output"]["min"] <= min(o) and max(o) <= m["output"]["max"]
    assert all(0 <= int(r.prompt.max()) < 1000 for r in tr.requests)


def test_open_loop_arrivals_span_warm_window_and_drain():
    m = MIXES["chat-poisson"]
    tr = T.generate(m, 9, 45.0, 1000, 60.0)
    rate, warm = m["arrival"]["rate_per_s"], m["warm_s"]
    n_warm, n_win = int(np.ceil(rate * warm)), int(np.ceil(rate * 45.0))
    due = np.array([s.due_s for s in tr.sessions])
    assert (due[:n_warm] < warm).all()
    assert ((due[n_warm:n_warm + n_win] >= warm)
            & (due[n_warm:n_warm + n_win] < warm + 45.0)).all()
    assert (due[n_warm + n_win:] >= warm + 45.0).all()
    assert (np.diff(due) >= 0).all() and due[0] == 0.0


def test_bursty_arrivals_keep_the_mean_rate():
    arr = {"kind": "bursty", "rate_per_s": 2.0, "burst": 4, "peak_x": 5}
    d = T._open_dues(arr, 60.0, 120, np.random.default_rng(1))
    assert len(d) == 120 and (np.diff(d) >= 0).all()
    assert 50.0 < d[-1] <= 60.0
    # within a burst the gaps are those of 5x the rate: much shorter
    gaps = np.diff(d)
    assert np.median(gaps) < 0.5 / 2.0 and gaps.max() > 1.0 / 2.0


def test_sessions_prefixes_and_mixture():
    m = MIXES["tiny-sessions"]
    tr = T.generate(m, 11, 10.0, 500, 5.0)
    assert all(len(s.turns) == 2 for s in tr.sessions)
    assert [r.turn for r in tr.sessions[0].turns] == [0, 1]
    firsts = [s.turns[0].prompt for s in tr.sessions]
    heads = {tuple(p[:16]) for p in firsts}
    assert len(heads) == 2                 # two shared prefix groups
    news = [len(r.prompt) for r in tr.requests if r.turn]
    assert 20 in news and min(news) >= 4 and max(news) <= 20
    assert T.max_context(m) == 16 + 2 * (20 + 16)
    with pytest.raises(ValueError, match="max_len"):
        T.generate(m, 11, 10.0, 500, 5.0, max_len=60)


def test_tenants_and_repeated_content():
    m = dict(MIXES["chat-poisson"], tenants={"n": 4, "zipf_s": 1.0},
             content={"kind": "repeat", "period": 8})
    tr = T.generate(m, 2, 20.0, 1000, 0.0)
    got = Counter(r.adapter_id for r in tr.requests)
    assert set(got) <= {0, 1, 2, 3} and got[0] > got[3]
    p = tr.requests[0].prompt
    assert len(p) > 8 and np.array_equal(p[:8], p[8:16])


def test_mixture_splits_by_weight():
    d = {"dist": "mixture", "parts": [
        {"weight": 0.8, "dist": "fixed", "value": 256},
        {"weight": 0.2, "dist": "fixed", "value": 8192}]}
    got = Counter(T.lengths(d, 50, 0).tolist())
    assert got == {256: 40, 8192: 10}


def test_lognormal_quantiles():
    d = T.LengthDist(1024, 0.8, 128, 4096)
    q = d.quantiles(101)
    assert q[50] == 1024 and q[0] >= 128 and q[-1] <= 4096
    assert (np.diff(q) >= 0).all()


def test_percentile_is_linear_interpolation():
    assert T.percentile([1, 2, 3, 4], 50) == 2.5
    assert T.percentile(list(range(101)), 95) == 95.0


def test_open_loop_blocks_share_the_work():
    m = dict(MIXES["chat-poisson"], arrival=dict(
        MIXES["chat-poisson"]["arrival"], block=19))
    tr = T.generate(m, 7, 51.0, 1000, 0.0)
    warm, n = m["warm_s"], int(np.ceil(m["arrival"]["rate_per_s"] * 51.0))
    sizes = T._largest_remainder(n, [1.0] * round(n / m["arrival"]["block"]))
    t = warm
    for size in sizes:
        end = t + 51.0 * size / n
        got = sorted(len(s.turns[0].prompt) for s in tr.sessions
                     if t <= s.due_s < end)
        assert got == sorted(T.lengths(m["prompt"], int(size), 0).tolist())
        t = end
