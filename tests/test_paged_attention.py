"""Paged flash attention: Pallas kernels vs the gather path (interpret
mode on CPU), end-to-end engine equivalence, and the analytical fusion
pricing of both attention impls."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.configs.base import Variant
from repro.core.workload import WorkloadModel
from repro.engine import Engine, EngineConfig, ForecastTwin, Request
from repro.kernels.paged_attention import (paged_attention, paged_decode,
                                          paged_prefill)
from repro.kernels.paged_attention.paged_attention import decode_schedule
from repro.kernels.paged_attention.ref import (paged_decode_ref,
                                               paged_prefill_ref)
from repro.launch.mesh import make_host_mesh
from repro.models import init_params
from repro.runtime import ShardingPolicy

KV_CODES = {jnp.float32: 0, jnp.bfloat16: 1, jnp.int8: 2}


def _rng(*ints):
    """A generator seeded by the test case itself, so each case draws the
    same data whatever order (or worker) the cases run in."""
    return np.random.default_rng([int(i) for i in ints])


def _pool(rng, N, bs, Hk, d, kv_dtype):
    """Head-major K/V pools ``(N, Hk, bs, d)``, as the engine stores them."""
    shape = (N, Hk, bs, d)
    if kv_dtype == jnp.int8:
        ck = jnp.asarray(rng.integers(-40, 40, shape), kv_dtype)
        cv = jnp.asarray(rng.integers(-40, 40, shape), kv_dtype)
    else:
        ck = jnp.asarray(rng.standard_normal(shape), kv_dtype)
        cv = jnp.asarray(rng.standard_normal(shape), kv_dtype)
    return ck, cv


def _tol(kv_dtype):
    """Absolute tolerance of kernel vs gather oracle.

    Both sides compute in f32 but in a different order (online softmax
    over blocks vs one softmax over the gathered span).  With int8 K/V in
    ±40 the scores reach ~1e2, where one f32 rounding is ~1e-5; that
    shifts each softmax weight by ~1e-5 relative, and values up to 40
    turn it into ~1e-3 absolute (the worst seen over 36 seeded prefill
    draws was 2.7e-4).  bf16 pools hold the oracle to bf16 resolution.
    """
    return {jnp.float32: 1e-4, jnp.bfloat16: 2e-2, jnp.int8: 1e-3}[kv_dtype]


# ---------------------------------------------------------------------------
# kernel vs gather-semantics oracle
# ---------------------------------------------------------------------------

def _tables(rng, S, N, nb, cursors):
    """Distinct blocks per slot; a freed slot (cursor 0) keeps a stale row
    that holds other slots' blocks, as the engine leaves it."""
    bt = rng.permutation(N)[:S * nb].reshape(S, nb)
    for s in range(S):
        if cursors[s] == 0 and S > 1:
            others = np.delete(bt, s, axis=0).ravel()
            bt[s] = np.roll(others, 1)[:nb]
    return bt


DECODE_CASES = [
    # (S, Hk, G, d, N, bs, nb, cursors) — cursors exercise block starts,
    # mid-block positions and a fresh slot (pos 0)
    (3, 2, 2, 32, 16, 8, 5, (0, 17, 39)),       # GQA, mid-block cursors
    (2, 4, 1, 64, 12, 16, 3, (16, 31)),         # MHA, block-aligned + last
    (4, 1, 4, 32, 18, 8, 4, (7, 8, 9, 30)),     # MQA around a block seam
    # 16 pages of 8 keys a compute block (test_decode_schedule_from_shapes),
    # 20 pages a table: cursors on the last key of the first compute block
    # and the first key of the second, which is a 4-page tail
    (2, 2, 2, 32, 48, 8, 20, (127, 128)),
    # a freed slot beside cursors in the tail block, one on the last key
    (3, 2, 2, 32, 64, 8, 20, (0, 150, 159)),
    (3, 4, 7, 32, 24, 8, 6, (5, 0, 47)),        # qwen2-7b's 4 x 7 heads
    (2, 8, 4, 16, 12, 8, 5, (39, 21)),          # granite-3's 8 x 4 heads
]


@pytest.mark.parametrize("case", DECODE_CASES, ids=[str(c) for c in DECODE_CASES])
@pytest.mark.parametrize("kv_dtype", [jnp.float32, jnp.bfloat16, jnp.int8])
def test_paged_decode_matches_gather_ref(case, kv_dtype):
    S, Hk, G, d, N, bs, nb, cursors = case
    rng = _rng(S, Hk, G, d, N, bs, nb, *cursors, KV_CODES[kv_dtype])
    q = jnp.asarray(rng.standard_normal((S, Hk, G, d)), jnp.float32)
    ck, cv = _pool(rng, N, bs, Hk, d, kv_dtype)
    bt = jnp.asarray(_tables(rng, S, N, nb, cursors), jnp.int32)
    pos = jnp.asarray(cursors, jnp.int32)
    out = paged_decode(q, ck, cv, bt, pos)
    ref = paged_decode_ref(q, ck, cv, bt, pos)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=_tol(kv_dtype))


@pytest.mark.parametrize("kv_dtype", [jnp.float32, jnp.bfloat16])
def test_paged_decode_reads_no_page_past_a_cursor(kv_dtype):
    """Every pool page that no slot reaches at or before its cursor is
    NaN: reserved pages past a cursor, a freed slot's stale row, the pool's
    unused pages.  A page read and weighted zero would still turn the
    output NaN, so a finite output equal to the clean pool's proves no
    such page is fetched or mixed in."""
    S, Hk, G, d, N, bs, nb = 4, 2, 2, 32, 96, 8, 20
    cursors = (140, 0, 37, 127)
    rng = _rng(S, N, bs, nb, *cursors, KV_CODES[kv_dtype])
    q = jnp.asarray(rng.standard_normal((S, Hk, G, d)), jnp.float32)
    ck, cv = _pool(rng, N, bs, Hk, d, kv_dtype)
    bt = _tables(rng, S, N, nb, cursors)
    reached = {int(bt[s, j]) for s in range(S)
               for j in range(cursors[s] // bs + 1)}
    dead = np.setdiff1d(np.arange(N), sorted(reached))
    assert len(dead) > N // 2
    bt, pos = jnp.asarray(bt, jnp.int32), jnp.asarray(cursors, jnp.int32)
    out = paged_decode(q, ck.at[dead].set(jnp.nan), cv.at[dead].set(jnp.nan),
                       bt, pos)
    ref = paged_decode_ref(q, ck, cv, bt, pos)
    assert np.isfinite(np.asarray(out, np.float32)).all()
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=_tol(kv_dtype))


def test_decode_schedule_from_shapes():
    """Pages and heads per decode step follow from the shapes alone."""
    # both cells' geometry, 128-token pages: 8 pages with every head
    assert decode_schedule(8, 128, 64, jnp.bfloat16, 32) == (8, 8)
    assert decode_schedule(4, 128, 128, jnp.bfloat16, 36) == (8, 4)
    assert decode_schedule(4, 128, 128, jnp.int8, 36) == (8, 4)
    # a narrow table caps the pages, and so does the operand count
    assert decode_schedule(2, 128, 64, jnp.bfloat16, 3) == (3, 2)
    assert decode_schedule(2, 8, 32, jnp.float32, 20) == (16, 2)
    # past the VMEM budget: fewer pages first, then a head tile that
    # divides the heads
    assert decode_schedule(16, 128, 128, jnp.float32, 32) == (2, 16)
    assert decode_schedule(24, 128, 256, jnp.float32, 32) == (1, 12)


@pytest.mark.parametrize("kv_dtype", [jnp.float32, jnp.bfloat16, jnp.int8])
def test_paged_decode_head_tiles(monkeypatch, kv_dtype):
    """Where a page of every head would not fit, a step takes a tile of
    heads; the grid then walks the tiles of each slot in turn."""
    S, Hk, G, d, N, bs, nb = 3, 4, 2, 32, 16, 8, 5
    cursors = (30, 0, 13)
    monkeypatch.setattr(paged_attention, "DECODE_KV_VMEM_BYTES", 40 << 10)
    assert decode_schedule(Hk, bs, d, jnp.float32, nb) == (1, 2)
    rng = _rng(S, Hk, N, *cursors, KV_CODES[kv_dtype])
    q = jnp.asarray(rng.standard_normal((S, Hk, G, d)), jnp.float32)
    ck, cv = _pool(rng, N, bs, Hk, d, kv_dtype)
    bt = jnp.asarray(_tables(rng, S, N, nb, cursors), jnp.int32)
    pos = jnp.asarray(cursors, jnp.int32)
    out = paged_attention.paged_decode_fwd(q, ck, cv, bt, pos,
                                           interpret=True)
    ref = paged_decode_ref(q, ck, cv, bt, pos)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=_tol(kv_dtype))


@pytest.mark.parametrize("kv_dtype", [jnp.float32, jnp.bfloat16, jnp.int8])
@pytest.mark.parametrize("start,valid", [(0, 16), (10, 13), (24, 5)])
def test_paged_prefill_matches_gather_ref(kv_dtype, start, valid):
    """Chunks at absolute positions: admission start, a mid-block chunk
    on top of cached history, and a small tail remainder chunk."""
    C, Hk, G, d = 16, 2, 2, 32
    N, bs, nb = 16, 8, 5
    rng = _rng(start, valid, KV_CODES[kv_dtype])
    q = jnp.asarray(rng.standard_normal((C, Hk, G, d)), jnp.float32)
    ck, cv = _pool(rng, N, bs, Hk, d, kv_dtype)
    table = jnp.asarray(rng.permutation(N)[:nb], jnp.int32)
    out = paged_prefill(q, ck, cv, table, jnp.int32(start), jnp.int32(valid))
    ref = paged_prefill_ref(q, ck, cv, table, start, valid)
    np.testing.assert_allclose(np.asarray(out[:valid], np.float32),
                               np.asarray(ref[:valid], np.float32),
                               atol=_tol(kv_dtype))


def test_paged_decode_shared_prefix_and_cow_tables():
    """Two slots map the same physical prefix blocks (radix hit) and a
    third holds a COW fork of the shared tail block: the kernel must read
    each table's physical blocks, shared or forked, identically to the
    gather."""
    Hk, G, d, bs, nb = 2, 2, 32, 8, 4
    N = 12
    rng = _rng(N, bs, nb)
    ck, cv = _pool(rng, N, bs, Hk, d, jnp.bfloat16)
    shared = [0, 1]                           # full shared prefix blocks
    bt = jnp.asarray([shared + [2, 3],        # first-comer
                      shared + [4, 5],        # prefix hit, own suffix
                      shared[:1] + [6, 7, 8]  # COW fork of block 1 -> 6
                      ], jnp.int32)
    # the fork duplicates the shared block before diverging mid-block
    ck = ck.at[6].set(ck[1])
    cv = cv.at[6].set(cv[1])
    q = jnp.asarray(rng.standard_normal((3, Hk, G, d)), jnp.float32)
    pos = jnp.asarray([25, 20, 12], jnp.int32)   # mid-block cursors
    out = paged_decode(q, ck, cv, bt, pos)
    ref = paged_decode_ref(q, ck, cv, bt, pos)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=1e-4)
    # reading through the fork ([0, 6]) == reading the original ([0, 1])
    # while the forked block is still an exact copy
    pos1 = jnp.asarray([12], jnp.int32)
    out_orig = paged_decode(q[:1], ck, cv, bt[:1, :2], pos1)
    out_fork = paged_decode(q[:1], ck, cv, bt[2:3, :2], pos1)
    np.testing.assert_allclose(np.asarray(out_orig, np.float32),
                               np.asarray(out_fork, np.float32), atol=1e-4)


# ---------------------------------------------------------------------------
# end-to-end: engine with attn_impl="paged" == attn_impl="gather"
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def mesh():
    return make_host_mesh()


@pytest.fixture(scope="module")
def cfg():
    return configs.reduced(configs.get("qwen2-7b"))


@pytest.fixture(scope="module")
def params_f32(cfg):
    # f32 params keep both read paths' numerics within argmax resolution
    p = init_params(cfg, jax.random.PRNGKey(0))
    return jax.tree_util.tree_map(
        lambda x: x.astype(jnp.float32)
        if jnp.issubdtype(x.dtype, jnp.floating) else x, p)


@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
def test_engine_paged_equals_gather_end_to_end(mesh, cfg, params_f32,
                                               kv_dtype):
    """Same requests, both attention impls, greedy: identical tokens —
    through chunked prefill (incl. tail chunks), prefix-cache hits with
    mid-block COW forks, and fused decode blocks."""
    prompts = np.array(jax.random.randint(
        jax.random.PRNGKey(3), (3, 19), 0, cfg.vocab_size, jnp.int32))
    prompts[1, :10] = prompts[0, :10]      # shared prefix -> radix hit + COW
    reqs = [Request(rid=i, prompt=list(prompts[i]), max_new=6)
            for i in range(3)]
    outs = {}
    for impl in ("gather", "paged"):
        with mesh:
            eng = Engine(cfg, params_f32, mesh, ShardingPolicy(),
                         EngineConfig(max_slots=2, max_len=40, chunk_size=8,
                                      decode_block=3, block_size=8,
                                      kv_dtype=kv_dtype, attn_impl=impl))
            outs[impl] = {r.rid: r.tokens for r in eng.run(reqs)}
    assert outs["gather"] == outs["paged"]


def test_engine_config_rejects_degenerate_geometry():
    """Explicit n_blocks=0 must raise, not silently fall back to the
    default pool; zero/negative step sizes are rejected too."""
    with pytest.raises(ValueError, match="n_blocks"):
        EngineConfig(max_slots=2, max_len=64, n_blocks=0)
    with pytest.raises(ValueError, match="chunk_size"):
        EngineConfig(max_slots=2, max_len=64, chunk_size=0)
    with pytest.raises(ValueError, match="decode_block"):
        EngineConfig(max_slots=2, max_len=64, decode_block=0)
    with pytest.raises(ValueError, match="block_size"):
        EngineConfig(max_slots=2, max_len=64, block_size=0)
    with pytest.raises(ValueError, match="max_slots"):
        EngineConfig(max_slots=0, max_len=64)
    with pytest.raises(ValueError, match="attn_impl"):
        EngineConfig(max_slots=2, max_len=64, attn_impl="flash")
    # a valid explicit pool still works
    assert EngineConfig(max_slots=2, max_len=64, n_blocks=3).pool_blocks == 3


# ---------------------------------------------------------------------------
# analytical fusion pricing of the two impls
# ---------------------------------------------------------------------------

def test_workload_attn_impl_pricing_ordering():
    """gather adds page-remat traffic on top of the plain model; paged
    fuses the attention core below it — so for an unfused variant:
    paged < none < gather in decode memory traffic."""
    arch = configs.get("llama2-7b")
    v = Variant(name="bf16", fused=False)
    t = {impl: WorkloadModel(arch, v, attn_impl=impl)
         .decode_step(4, 512).totals("decode")
         for impl in (None, "gather", "paged")}
    assert t["paged"].mem_total < t[None].mem_total < t["gather"].mem_total
    # compute is identical: both impls do the same MACs
    assert t["paged"].ops == pytest.approx(t[None].ops)
    assert t["gather"].ops == pytest.approx(t[None].ops)
    # the remat delta is exactly the K+V span (past + the new token),
    # read + written, per layer
    kv_span = 2 * 513 * arch.n_kv_heads * arch.head_dim * 2  # bf16 bytes
    n_attn = sum(1 for k in arch.block_kinds() if k == "attn")
    assert (t["gather"].mem_total - t[None].mem_total
            == pytest.approx(4 * 2 * kv_span * n_attn))


def test_workload_attn_impl_affine_identity():
    """decode_totals_mixed == decode_step for uniform batches under both
    pricing modes (the memoized twin depends on this)."""
    arch = configs.get("llama2-7b")
    for impl in ("gather", "paged"):
        wm = WorkloadModel(arch, Variant(name="bf16"), attn_impl=impl)
        direct = wm.decode_step(3, 100).totals("decode")
        mixed = wm.decode_totals_mixed([100, 100, 100])
        assert mixed.mem_total == pytest.approx(direct.mem_total, rel=1e-9)
        assert mixed.ops == pytest.approx(direct.ops, rel=1e-9)
        assert mixed.dispatches == direct.dispatches


def test_workload_rejects_unknown_attn_impl():
    with pytest.raises(ValueError, match="attn_impl"):
        WorkloadModel(configs.get("llama2-7b"), attn_impl="flash")


def test_twin_prices_paged_below_gather_on_same_trace():
    """The same replayed schedule must forecast faster with the paged
    kernels than with the gather path — the delta the ROADMAP wants to
    be a forecastable quantity."""
    from repro.core import hardware
    from repro.engine.scheduler import TraceEvent
    arch = configs.get("qwen2-7b")
    trace = [
        TraceEvent(kind="engine", chunk=16, n_steps=8),
        TraceEvent(kind="prefill_chunk", rid=0, slot=0, chunk=16,
                   past_len=0, last=False),
        TraceEvent(kind="prefill_chunk", rid=0, slot=0, chunk=16,
                   past_len=16, last=True),
        TraceEvent(kind="decode_block", n_steps=8, slots=((0, 32, 9),)),
    ]
    hw = hardware.get("tpu-v5e")
    tf = {}
    for impl in ("gather", "paged"):
        twin = ForecastTwin(arch, hw, block_size=16, attn_impl=impl)
        tf[impl] = twin.replay(trace)
    assert tf["paged"].total_time < tf["gather"].total_time
    assert tf["paged"].tps > tf["gather"].tps
    # both replays executed the same schedule
    assert tf["paged"].total_tokens == tf["gather"].total_tokens == 9
