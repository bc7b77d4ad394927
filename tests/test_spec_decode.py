"""Speculative decoding: drafter proposals, the batched multi-query
verify (kernel + engine), T=0 bit-identity with plain greedy decode, and
the analytical pricing (verify step, speedup curve, break-even α, twin
replay of measured acceptance)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.configs.base import Variant
from repro.core import Forecaster, WorkloadModel, hardware
from repro.engine import (Engine, EngineConfig, ForecastTwin,
                          NgramDrafter, Request, despeculate_trace,
                          make_drafter)
from repro.kernels.paged_attention import paged_verify
from repro.kernels.paged_attention.ref import paged_verify_ref
from repro.launch.mesh import make_host_mesh
from repro.models import init_params
from repro.runtime import ShardingPolicy

RNG = np.random.default_rng(23)


@pytest.fixture(scope="module")
def mesh():
    return make_host_mesh()


@pytest.fixture(scope="module")
def cfg():
    return configs.reduced(configs.get("qwen2-7b"))


@pytest.fixture(scope="module")
def params(cfg):
    return init_params(cfg, jax.random.PRNGKey(0))


# ---------------------------------------------------------------------------
# drafter
# ---------------------------------------------------------------------------

def test_ngram_drafter_follows_cycle():
    """A trailing n-gram that occurred before proposes the tokens that
    followed it — the drafter locks onto periodic context."""
    d = NgramDrafter(n=3)
    motif = [5, 9, 2, 7]
    toks = motif * 4                       # ends ...5 9 2 7; next is 5 9 2 7
    assert d.propose(toks, 4) == motif
    # continuation runs dry at the history's end → pads with its last token
    assert d.propose(toks, 6) == motif + [7, 7]


def test_ngram_drafter_always_proposes_k():
    d = NgramDrafter(n=3)
    for toks in ([1], [1, 2], list(range(16))):   # no repeats anywhere
        out = d.propose(toks, 4)
        assert len(out) == 4                      # pads, never comes short
    assert len(d.propose([3, 3, 3, 3], 5)) == 5


def test_make_drafter_variants(cfg):
    assert make_drafter(None).draft_arch is None
    small = make_drafter("qwen2-7b", reduce=True,
                         vocab_size=cfg.vocab_size)
    assert small.draft_arch is not None
    assert len(small.propose([1, 2, 3, 4], 3)) == 3


# ---------------------------------------------------------------------------
# paged verify kernel vs oracle
# ---------------------------------------------------------------------------

VERIFY_CASES = [
    # (S, Q, Hk, G, d, N, bs, nb, cursors)
    (2, 5, 2, 2, 32, 16, 8, 5, (3, 17)),          # GQA, mid-block
    (3, 3, 1, 4, 32, 18, 8, 4, (0, 8, 23)),       # MQA, seam + fresh slot
    (2, 4, 4, 1, 64, 12, 16, 3, (16, 29)),        # MHA, aligned + near-end
]


@pytest.mark.parametrize("case", VERIFY_CASES,
                         ids=[str(c) for c in VERIFY_CASES])
def test_paged_verify_matches_ref(case):
    S, Q, Hk, G, d, N, bs, nb, cursors = case
    q = jnp.asarray(RNG.standard_normal((S, Q, Hk, G, d)), jnp.float32)
    ck = jnp.asarray(RNG.standard_normal((N, Hk, bs, d)), jnp.float32)
    cv = jnp.asarray(RNG.standard_normal((N, Hk, bs, d)), jnp.float32)
    bt = jnp.asarray(RNG.permutation(N)[:S * nb].reshape(S, nb), jnp.int32)
    pos = jnp.asarray(cursors, jnp.int32)
    out = paged_verify(q, ck, cv, bt, pos)
    ref = paged_verify_ref(q, ck, cv, bt, pos)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-4)


def test_paged_verify_q1_is_decode():
    """A 1-query verify is exactly a decode step (the k=0 degeneracy at
    the kernel level)."""
    from repro.kernels.paged_attention import paged_decode
    S, Hk, G, d, N, bs, nb = 2, 2, 2, 32, 12, 8, 4
    q = jnp.asarray(RNG.standard_normal((S, Hk, G, d)), jnp.float32)
    ck = jnp.asarray(RNG.standard_normal((N, Hk, bs, d)), jnp.float32)
    cv = jnp.asarray(RNG.standard_normal((N, Hk, bs, d)), jnp.float32)
    bt = jnp.asarray(RNG.permutation(N)[:S * nb].reshape(S, nb), jnp.int32)
    pos = jnp.asarray((5, 19), jnp.int32)
    one = paged_verify(q[:, None], ck, cv, bt, pos)[:, 0]
    dec = paged_decode(q, ck, cv, bt, pos)
    np.testing.assert_allclose(np.asarray(one), np.asarray(dec), atol=1e-5)


# ---------------------------------------------------------------------------
# engine: T=0 speculative decode is bit-identical to plain greedy
# ---------------------------------------------------------------------------

def _spec_requests(cfg):
    """3 requests through 2 slots: rid 2 repeats rid 0's prompt (queued
    behind it → full-prompt prefix hit + COW tail fork), rid 1 shares a
    16-token prefix — speculation must stay exact through hits and forks."""
    rng = np.random.default_rng(3)
    motif = rng.integers(0, cfg.vocab_size, 6).tolist()
    p0 = (motif * 4)[:24]
    p1 = p0[:16] + rng.integers(0, cfg.vocab_size, 8).tolist()
    return [Request(rid=0, prompt=p0, max_new=10),
            Request(rid=1, prompt=p1, max_new=10),
            Request(rid=2, prompt=list(p0), max_new=10)]


@pytest.mark.parametrize("attn_impl", ["gather", "paged"])
def test_spec_t0_bit_identical_to_greedy(mesh, cfg, params, attn_impl):
    reqs = _spec_requests(cfg)
    outs = {}
    for k in (0, 4):
        ec = EngineConfig(max_slots=2, max_len=64, chunk_size=8,
                          decode_block=4, block_size=8,
                          attn_impl=attn_impl, spec_k=k)
        with mesh:
            eng = Engine(cfg, params, mesh, ShardingPolicy(), ec)
            results = eng.run([dataclasses.replace(r) for r in reqs])
        outs[k] = [r.tokens for r in results]
        if k:
            assert eng.spec_steps > 0 and eng.spec_proposed > 0
            assert 0.0 <= eng.spec_acceptance <= 1.0
            assert eng.spec_tokens_per_step >= 1.0
    assert outs[4] == outs[0]            # accepted tokens == greedy decode


def test_spec_trace_metadata(mesh, cfg, params):
    """The trace header records the engine knobs and every spec_step
    carries per-slot proposed/accepted counts consistent with emission."""
    reqs = _spec_requests(cfg)
    ec = EngineConfig(max_slots=2, max_len=64, chunk_size=8,
                      decode_block=4, block_size=8, spec_k=3)
    with mesh:
        eng = Engine(cfg, params, mesh, ShardingPolicy(), ec)
        results = eng.run(reqs)
    header = eng.trace[0]
    assert header.kind == "engine"
    assert header.attn_impl == "gather"
    assert header.block_size == 8 and header.spec_k == 3
    steps = [e for e in eng.trace if e.kind == "spec_step"]
    assert steps and all(e.spec_k == 3 for e in steps)
    emitted = {r.rid: 0 for r in results}
    for ev in steps:
        assert len(ev.proposed) == len(ev.slots) == len(ev.accepted)
        for (rid, _, _), prop, acc in zip(ev.slots, ev.proposed,
                                          ev.accepted):
            assert 0 <= acc <= prop <= 3
            emitted[rid] += acc
    assert sum(emitted.values()) == eng.spec_accepted
    # every request still hit its budget exactly
    assert all(len(r.tokens) == 10 for r in results)


# ---------------------------------------------------------------------------
# analytical: verify pricing, speedup curve, break-even
# ---------------------------------------------------------------------------

ARCH = configs.get("llama2-7b")


def test_verify_step_k0_is_decode_step():
    wm = WorkloadModel(ARCH, Variant(fused=True))
    a = wm.verify_step(2, 333, 0).totals("decode")
    b = wm.decode_step(2, 333).totals("decode")
    for f in ("ops", "mem_rd", "mem_wr", "kv_rd", "kv_wr", "dispatches"):
        assert getattr(a, f) == pytest.approx(getattr(b, f), rel=1e-12)


def test_verify_totals_mixed_identities():
    wm = WorkloadModel(ARCH, Variant())
    pls = (100, 200, 333)
    a, b = wm.verify_totals_mixed(pls, 0), wm.decode_totals_mixed(pls)
    assert a.ops == pytest.approx(b.ops) and a.mem_total == pytest.approx(
        b.mem_total)
    # uniform mixed == the direct uniform verify step
    for B, p, k in ((1, 64, 4), (3, 256, 2)):
        mixed = wm.verify_totals_mixed([p] * B, k)
        direct = wm.verify_step(B, p, k).totals("decode")
        for f in ("ops", "mem_rd", "mem_wr", "dispatches"):
            assert getattr(mixed, f) == pytest.approx(
                getattr(direct, f), rel=1e-9), (B, p, k, f)


def test_verify_amortizes_weight_reads():
    """k+1 queries reread the weights once: a verify step costs far less
    memory traffic than k+1 decode steps, but strictly more than one."""
    wm = WorkloadModel(ARCH, Variant())
    k = 4
    one = wm.decode_step(1, 512).totals("decode").mem_total
    ver = wm.verify_step(1, 512, k).totals("decode").mem_total
    assert one < ver < (k + 1) * one * 0.5


def test_spec_expected_tokens():
    f = Forecaster.spec_expected_tokens
    assert f(0, 0.5) == 1.0
    assert f(4, 0.0) == 1.0
    assert f(4, 1.0) == 5.0
    assert f(2, 0.5) == pytest.approx(1.75)
    with pytest.raises(ValueError):
        f(2, 1.5)


def test_spec_speedup_monotone_and_k0_degenerate():
    wm = WorkloadModel(ARCH, Variant())
    fc = Forecaster(hardware.TPU_V5E)
    base = wm.decode_totals_mixed([512])
    ver = wm.verify_totals_mixed([512], 4)
    curve = fc.spec_speedup_curve(base, ver, 4,
                                  [i / 10 for i in range(11)], em=0.8)
    ups = [s for _, s in curve]
    assert all(b > a for a, b in zip(ups, ups[1:]))   # monotone in α
    # k=0 with verify==decode totals degenerates to the plain TPOT
    assert fc.spec_tpot(base, 0, 0.7, em=0.8) == pytest.approx(
        fc.step_latency(base, em=0.8))


def test_spec_breakeven_edges_and_crossing():
    wm = WorkloadModel(ARCH, Variant())
    fc = Forecaster(hardware.TPU_V5E)
    base = wm.decode_totals_mixed([512])
    ver = wm.verify_totals_mixed([512], 4)
    # ratio <= 1 (verify priced as the plain step): can never lose
    assert fc.spec_breakeven_acceptance(base, base, 4) == 0.0
    # a draft as expensive as the target pushes ratio past k+1: never wins
    assert fc.spec_breakeven_acceptance(base, base, 4,
                                        draft_totals=base) is None
    # a mid-cost draft crosses in (0, 1) and the speedup there is 1.0
    half = base.scaled(0.5)
    a = fc.spec_breakeven_acceptance(base, ver, 4, draft_totals=half,
                                     em=0.8)
    assert a is not None and 0.0 < a < 1.0
    assert fc.spec_speedup(base, ver, 4, a, draft_totals=half,
                           em=0.8) == pytest.approx(1.0, rel=1e-6)


# ---------------------------------------------------------------------------
# twin: AUTO header resolution, spec replay, despeculation
# ---------------------------------------------------------------------------

def _spec_trace(mesh, cfg, params, spec_k=4):
    ec = EngineConfig(max_slots=2, max_len=64, chunk_size=8,
                      decode_block=4, block_size=8, spec_k=spec_k)
    with mesh:
        eng = Engine(cfg, params, mesh, ShardingPolicy(), ec)
        eng.run(_spec_requests(cfg))
    return eng, tuple(eng.trace)


def test_twin_auto_resolves_trace_header(mesh, cfg, params):
    eng, trace = _spec_trace(mesh, cfg, params)
    auto = ForecastTwin(cfg, hardware.TPU_V5E, Variant(), em=0.8)
    explicit = ForecastTwin(cfg, hardware.TPU_V5E, Variant(), em=0.8,
                            attn_impl="gather", block_size=8)
    plain = ForecastTwin(cfg, hardware.TPU_V5E, Variant(), em=0.8,
                         attn_impl=None)
    assert auto.replay(trace).total_time == pytest.approx(
        explicit.replay(trace).total_time, rel=1e-12)
    # the un-priced twin is strictly cheaper (no gather page remat)
    assert plain.replay(trace).total_time < explicit.replay(
        trace).total_time


def test_twin_spec_replay_and_despeculate(mesh, cfg, params):
    eng, trace = _spec_trace(mesh, cfg, params)
    twin = ForecastTwin(cfg, hardware.TPU_V5E, Variant(), em=0.8,
                        attn_impl=None)
    fc = twin.replay(trace)
    assert fc.total_tokens == sum(len(r.tokens)
                                  for r in eng.results.values())
    despec = despeculate_trace(trace)
    assert all(e.kind != "spec_step" for e in despec)
    assert despec[0].spec_k == 0
    plain = twin.replay(despec)
    # the rewrite preserves every emitted token and all prefill work
    assert plain.total_tokens == fc.total_tokens
    assert plain.prefill_time == pytest.approx(fc.prefill_time, rel=1e-12)
    # verify latency: k=0 verify == decode step; k>0 strictly dearer
    pls = [24, 24]
    assert twin.verify_step_latency(pls, 0) == pytest.approx(
        twin.decode_step_latency(pls), rel=1e-12)
    assert twin.verify_step_latency(pls, 4) > twin.decode_step_latency(pls)


def test_twin_draft_arch_prices_extra(mesh, cfg, params):
    _, trace = _spec_trace(mesh, cfg, params)
    free = ForecastTwin(cfg, hardware.TPU_V5E, Variant(), em=0.8,
                        attn_impl=None)
    paid = ForecastTwin(cfg, hardware.TPU_V5E, Variant(), em=0.8,
                        attn_impl=None, draft_arch=cfg)
    assert paid.replay(trace).total_time > free.replay(trace).total_time


# ---------------------------------------------------------------------------
# scenario plumbing
# ---------------------------------------------------------------------------

def test_scenario_spec_roundtrip():
    from repro.api import Scenario
    s = Scenario(model="llama2-7b").spec_decode(4, 0.6)
    assert (s.spec_k, s.spec_acceptance, s.spec_draft_arch) == (4, 0.6,
                                                                None)
    s2 = Scenario.from_dict(s.to_dict())
    assert s2 == s
    with pytest.raises(ValueError):
        Scenario(model="llama2-7b", spec_k=-1)
    with pytest.raises(ValueError):
        Scenario(model="llama2-7b", spec_acceptance=1.5)
    with pytest.raises(KeyError):
        Scenario(model="llama2-7b", spec_draft_arch="nope")
    with pytest.raises(ValueError):
        Scenario(model="llama2-7b", prompt_len=8, prompt_motif_len=9)


# ---------------------------------------------------------------------------
# property: the speedup curve is well-behaved for any k and cost ratio
# ---------------------------------------------------------------------------

def test_spec_breakeven_consistent_property():
    pytest.importorskip(
        "hypothesis",
        reason="optional dev dependency (pip install hypothesis)")
    from hypothesis import given, settings, strategies as st

    fc = Forecaster(hardware.TPU_V5E)
    wm = WorkloadModel(ARCH, Variant())
    base = wm.decode_totals_mixed([512])

    @settings(max_examples=30, deadline=None)
    @given(k=st.integers(1, 8), a=st.floats(0.0, 1.0),
           b=st.floats(0.0, 1.0))
    def prop(k, a, b):
        ver = wm.verify_totals_mixed([512], k)
        lo, hi = sorted((a, b))
        s_lo = fc.spec_speedup(base, ver, k, lo, em=0.8)
        s_hi = fc.spec_speedup(base, ver, k, hi, em=0.8)
        assert s_hi >= s_lo                      # monotone in α
        assert fc.spec_expected_tokens(k, hi) <= k + 1
        star = fc.spec_breakeven_acceptance(base, ver, k, em=0.8)
        if star is not None and 0.0 < star < 1.0:
            assert fc.spec_speedup(base, ver, k, star,
                                   em=0.8) == pytest.approx(1.0, rel=1e-6)

    prop()
