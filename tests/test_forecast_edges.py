"""Forecaster edge paths: compute-bound TPOT (Eq. 4 with the ec term) and
BMM tile-efficiency asymptotics (§5.4.1)."""
import pytest

from repro.core import (Forecaster, StatsDB, hardware,
                        bmm_asymptotic_efficiency, bmm_tile_efficiency)


def _decode_db(ops, mem, dispatches):
    db = StatsDB()
    db.set_phase("decode")
    db.record("gemm", ops=ops, mem_rd=mem / 2, mem_wr=mem / 2,
              dispatches=dispatches)
    return db


def test_tpot_default_is_memory_bound():
    hw = hardware.TPU_V5E
    db = _decode_db(ops=1e9, mem=8e9, dispatches=10)
    fc = Forecaster(hw)
    expected = 8e9 / hw.bw + 10 * hw.dispatch_latency_s
    assert fc.tpot(db) == pytest.approx(expected, rel=1e-12)


def test_tpot_ec_switches_to_compute_bound():
    """With the optional ec term, TPOT = max(t_c, t_m) + t_d — a huge ops
    total must dominate the tiny memory term."""
    hw = hardware.TPU_V5E
    db = _decode_db(ops=1e18, mem=16.0, dispatches=3)
    fc = Forecaster(hw)
    t_c = 1e18 / hw.flops
    t_d = 3 * hw.dispatch_latency_s
    got = fc.tpot(db, ec=1.0)
    assert got == pytest.approx(t_c + t_d, rel=1e-12)
    assert got > fc.tpot(db)                       # memory-only path is tiny
    # halving compute efficiency doubles the compute term
    assert fc.tpot(db, ec=0.5) == pytest.approx(2 * t_c + t_d, rel=1e-12)
    # ec supplied but memory still dominates -> unchanged from default
    db_m = _decode_db(ops=1.0, mem=8e9, dispatches=0)
    assert fc.tpot(db_m, ec=1.0) == pytest.approx(fc.tpot(db_m), rel=1e-12)


def test_tps_inverts_tpot_on_compute_bound_path():
    hw = hardware.TPU_V5E
    db = _decode_db(ops=1e18, mem=16.0, dispatches=0)
    fc = Forecaster(hw)
    assert fc.tps(db, ec=1.0) == pytest.approx(1.0 / fc.tpot(db, ec=1.0))


# ---------------------------------------------------------------------------
# BMM tile-padding efficiency asymptote (Fig. 8 / §5.4.1)
# ---------------------------------------------------------------------------

def test_bmm_tile_efficiency_saturates_at_multiples():
    assert bmm_tile_efficiency(128, 128) == 1.0
    assert bmm_tile_efficiency(129, 128) == pytest.approx(129 / 256)


def test_bmm_asymptotic_efficiency_converges_to_one():
    tile = 128
    short = bmm_asymptotic_efficiency(1, 10, tile)
    mid = bmm_asymptotic_efficiency(1, 1_000, tile)
    long = bmm_asymptotic_efficiency(1, 100_000, tile)
    assert short < mid < long < 1.0
    assert long > 0.995
    # the mean can never beat perfect tiling nor fall under the worst tile
    assert 1.0 / tile <= short <= 1.0
    # prompt already huge => every step is near-perfect regardless of n_new
    assert bmm_asymptotic_efficiency(10_000_000, 100, tile) > 0.999


# ---------------------------------------------------------------------------
# ForecastTwin replay edges: cold_trace backfill + decode memoization
# ---------------------------------------------------------------------------

def _warm_trace(chunk_size, prompt, cached, n_req):
    """A trace where EVERY admission is a prefix hit whose suffix fits one
    small tail chunk — no full-size chunk ever appears in the trace."""
    from repro.engine.scheduler import TraceEvent
    evs = [TraceEvent(kind="engine", chunk=chunk_size, n_steps=4)]
    for rid in range(n_req):
        evs.append(TraceEvent(kind="prefill_chunk", rid=rid, slot=0,
                              chunk=prompt - cached, past_len=cached,
                              cached=cached, last=True))
        evs.append(TraceEvent(kind="decode_block", n_steps=4,
                              slots=((rid, prompt, 5),)))
    return evs


def test_cold_trace_backfills_at_engine_chunk_size():
    """Regression: with an all-warm trace the largest observed chunk is a
    tiny tail remainder; backfill must use the chunk_size recorded in the
    trace header, not max(ev.chunk)."""
    from repro.engine import cold_trace
    chunk_size, prompt, cached = 16, 34, 32
    trace = _warm_trace(chunk_size, prompt, cached, n_req=2)
    cold = cold_trace(trace)
    chunks0 = [ev for ev in cold
               if ev.kind == "prefill_chunk" and ev.rid == 0]
    # [0,32) backfilled in chunk_size steps + the original 2-token suffix
    assert [(ev.past_len, ev.chunk) for ev in chunks0] == [
        (0, 16), (16, 16), (32, 2)]
    assert all(ev.cached == 0 for ev in cold if ev.kind == "prefill_chunk")
    # pre-header traces (no "engine" event) keep the legacy estimate
    legacy = cold_trace(trace[1:])
    chunks0 = [ev for ev in legacy
               if ev.kind == "prefill_chunk" and ev.rid == 0]
    assert [(ev.past_len, ev.chunk) for ev in chunks0] == [
        (0, 2), (2, 2)] + [(p, 2) for p in range(4, 32, 2)] + [(32, 2)]


def test_cold_trace_replay_prices_full_prompt():
    """The cold counterfactual of an all-warm trace must prefill every
    prompt token — the TTFT-savings forecast rests on this superset."""
    from repro import configs
    from repro.engine import ForecastTwin, cold_trace
    arch = configs.get("qwen2-7b")
    trace = _warm_trace(16, 34, 32, n_req=2)
    twin = ForecastTwin(arch, hardware.get("tpu-v5e"), block_size=16)
    warm, cold = twin.replay(trace), twin.replay(cold_trace(trace))
    assert warm.cached_tokens == 64 and cold.cached_tokens == 0
    assert warm.prompt_tokens == cold.prompt_tokens == 68
    assert cold.prefill_time > warm.prefill_time
    assert cold.mean_ttft > warm.mean_ttft


def test_twin_decode_memoization_bit_for_bit():
    """Memoized replay must agree exactly with a memo-free twin across
    repeated, permuted and distinct mixed batches (the memo key captures
    the affine identity of decode_totals_mixed plus table-entry counts)."""
    from repro import configs
    from repro.engine import ForecastTwin
    arch = configs.get("qwen2-7b")
    hw = hardware.get("tpu-v5e")
    batches = [(100, 200, 300), (300, 100, 200), (101, 199, 300),
               (100, 200, 300), (50,), (50, 50), (49, 51)]
    memo = ForecastTwin(arch, hw, block_size=16)
    got = [memo.decode_step_latency(b) for b in batches]
    want = [ForecastTwin(arch, hw, block_size=16).decode_step_latency(b)
            for b in batches]
    assert got == want                       # bit-for-bit, not approx
    # permutations and equal (B, sum, entries) keys collapse to one entry
    assert len(memo._decode_memo) == len(
        {memo._decode_memo_key(b) for b in batches})
    assert memo._decode_memo_key((100, 200, 300)) == \
        memo._decode_memo_key((300, 100, 200))
    # ...but equal sums with different table-entry totals do not:
    # (15, 17) reads 1+2 block-table entries, (16, 16) reads 2+2
    assert memo._decode_memo_key((15, 17)) != memo._decode_memo_key((16, 16))


def test_device_kind_table_names_v5e_and_rejects_unknown_kinds():
    """Forecasts next to a chip run look the chip up by ``device_kind``;
    a kind with no spec is an error, never a silent default."""
    assert hardware.for_device_kind("TPU v5 lite") is hardware.TPU_V5E
    with pytest.raises(KeyError, match="device kind"):
        hardware.for_device_kind("cpu")
