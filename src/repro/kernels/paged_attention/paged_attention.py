"""Paged flash attention Pallas TPU kernels (decode + chunked prefill).

The serving engine stores KV in a global head-major block pool
``(N, Hk, bs, hd)`` addressed through per-slot block tables.  The XLA
engine path gathers each
slot's blocks back into a contiguous ``(L_virt, Hk, hd)`` page buffer per
layer per step — exactly the HBM materialization the paper's canonical
fusion example (flash attention, §3.2.1) exists to elide.  These kernels
read K/V *block-by-block through the block table* with online softmax:

* the block table (and per-slot cursors) are scalar-prefetch operands, so
  a KV BlockSpec index map resolves a table entry to a physical block id
  before the DMA is issued — no page buffer ever exists in HBM;
* GQA is native: each step processes a KV head's whole query group, so
  repeated KV is never materialized;
* int8 KV dequantizes in-kernel (``astype`` on the VMEM-resident block),
  matching the engine's cast-based KV compression (§3.3.3).

Decode (:func:`paged_decode_fwd`) takes a slot, all its KV heads and a
compute block of several pages in one grid step, with the pages per
block and the heads per step derived from the shapes
(:func:`decode_schedule`).  It fetches only pages at or before each
slot's cursor: a page past it (reserved for the slot's output, or a freed
slot's stale entry) is never copied, and a block wholly past it costs a
grid step with no copy and no compute.

Prefill and verify walk one page of one KV head a grid step, over the
whole table: KV blocks past the queries are skipped with ``pl.when``,
which skips their MXU work but not their DMA.

The pool is head-major so that a K/V block is ``(1, heads, bs, hd)``:
its last two dimensions are the whole ``(bs, hd)`` tile of the array,
which Mosaic accepts for any ``bs``.  A token-major pool
``(N, bs, Hk, hd)`` would need a ``(1, bs, 1, hd)`` block, whose
second-minor dimension (one of ``Hk`` heads) the TPU compiler refuses.
For the same reason the decode kernel's pages are pipelined BlockSpec
operands rather than copies it issues itself: Mosaic refuses a manual
DMA of a pool page whose ``hd`` is narrower than the 128-lane tile (a
``hd`` of 64, say).

The KV-block grid dimension is minor-most so the VMEM accumulators
persist across KV steps (sequential grid execution on TPU; see
``kernels/flash_attention`` for the same schedule over dense K/V).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# decode: one query token for every slot, each against its own block table
# ---------------------------------------------------------------------------

#: keys one grid step of the decode kernel aims to cover (8 pages of 128)
DECODE_KEYS_PER_STEP = 1024
#: pages a step may take: each is a K and a V operand of its own
DECODE_MAX_PAGES_PER_STEP = 16
#: VMEM for the decode kernel's double-buffered K and V pages
DECODE_KV_VMEM_BYTES = 8 << 20


def decode_schedule(n_kv_heads: int, block_size: int, head_dim: int,
                    kv_dtype, max_bps: int) -> tuple[int, int]:
    """Pages per compute block and KV heads per grid step of the decode
    kernel, from the shapes alone.

    A step takes every KV head and ``DECODE_KEYS_PER_STEP // block_size``
    pages (at most the table's width and ``DECODE_MAX_PAGES_PER_STEP``).
    Where the double-buffered K and V pages would pass
    ``DECODE_KV_VMEM_BYTES`` (counted as VMEM holds them: rows padded to
    the dtype's sublane tile, lanes to 128), the step takes fewer pages,
    and below one page fewer heads: the largest divisor of ``n_kv_heads``
    that fits.
    """
    itemsize = jnp.dtype(kv_dtype).itemsize
    tile = 32 // itemsize
    page_head = (-(-block_size // tile) * tile) * (-(-head_dim // 128) * 128) \
        * itemsize
    pages = max(1, min(max_bps, DECODE_MAX_PAGES_PER_STEP,
                       DECODE_KEYS_PER_STEP // block_size))
    heads = n_kv_heads

    def fits():
        return 2 * 2 * pages * heads * page_head <= DECODE_KV_VMEM_BYTES

    while pages > 1 and not fits():
        pages -= 1
    while heads > 1 and not fits():
        heads = max(h for h in range(1, heads) if n_kv_heads % h == 0)
    return pages, heads


def _fetch_table(block_tables: jax.Array, pos: jax.Array, bs: int,
                 pages: int) -> jax.Array:
    """The pool page each of a grid step's ``pages`` K/V operands holds,
    for every (slot, compute block) in grid order, flattened.

    A page at or before the slot's cursor is the table's.  Any other
    operand keeps the page it held at the last step before (slot 0's
    first page before there is one), so its block index does not change
    and the pipeline issues no copy: pages past a cursor, stale or
    reserved, are never read.
    """
    S, nb = block_tables.shape
    col = jnp.arange(pl.cdiv(nb, pages) * pages, dtype=jnp.int32)
    live = col[None] <= jnp.minimum(pos // bs, nb - 1)[:, None]
    page = block_tables[:, jnp.minimum(col, nb - 1)]
    live, page = live.reshape(-1, pages), page.reshape(-1, pages)
    step = jnp.arange(1, live.shape[0] + 1, dtype=jnp.int32)[:, None]
    held = jax.lax.cummax(jnp.where(live, step, 0), axis=0)
    page = jnp.concatenate(
        [jnp.broadcast_to(block_tables[0, 0], (1, pages)), page])
    return jnp.take_along_axis(page, held, axis=0).reshape(-1)


def _decode_kernel(fetch_ref, pos_ref, q_ref, *refs, bs: int, pages: int,
                   n_blocks: int, scale: float, qk_dtype):
    k_refs, v_refs = refs[:pages], refs[pages:2 * pages]
    o_ref, acc_ref, m_ref, l_ref = refs[2 * pages:]
    s, c = pl.program_id(0), pl.program_id(2)
    pos = pos_ref[s]                               # slot cursor: key at
                                                   # ``pos`` was just written
    last_blk = jnp.minimum(pos // bs, n_blocks - 1) // pages

    @pl.when(c <= last_blk)
    def _compute():
        @pl.when(c == 0)
        def _init():
            acc_ref[...] = jnp.zeros_like(acc_ref)
            m_ref[...] = jnp.full_like(m_ref, NEG_INF)
            l_ref[...] = jnp.zeros_like(l_ref)

        # every head of the step in one batched product per page
        q = q_ref[0].astype(qk_dtype)                          # (H, G, d)
        scores, masks = [], []
        for j, k_ref in enumerate(k_refs):
            sc = jnp.einsum("hgd,hkd->hgk", q,                 # int8 KV
                            k_ref[0].astype(qk_dtype),         # dequantizes
                            preferred_element_type=jnp.float32) * scale
            k_pos = (c * pages + j) * bs + jax.lax.broadcasted_iota(
                jnp.int32, sc.shape, 2)
            masks.append(k_pos <= pos)
            scores.append(jnp.where(masks[-1], sc, NEG_INF))
        m_prev = m_ref[...]
        m_new = functools.reduce(jnp.maximum, [
            jnp.max(sc, axis=2, keepdims=True) for sc in scores], m_prev)
        alpha = jnp.exp(m_prev - m_new)
        probs = [jnp.where(mask, jnp.exp(sc - m_new), 0.0)
                 for sc, mask in zip(scores, masks)]
        l_ref[...] = l_ref[...] * alpha + sum(
            jnp.sum(p, axis=2, keepdims=True) for p in probs)
        acc_ref[...] = acc_ref[...] * alpha + sum(
            jnp.einsum("hgk,hkd->hgd", p, v_ref[0].astype(jnp.float32),
                       preferred_element_type=jnp.float32)
            for p, v_ref in zip(probs, v_refs))
        m_ref[...] = m_new

        @pl.when(c == last_blk)
        def _finalize():
            l = jnp.maximum(l_ref[...], 1e-30)
            o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def paged_decode_fwd(
    q: jax.Array,            # (S, Hk, G, d) one query token per slot
    cache_k: jax.Array,      # (N, Hk, bs, d) global block pool
    cache_v: jax.Array,      # (N, Hk, bs, d)
    block_tables: jax.Array,  # (S, max_bps) int32 physical block ids
    pos: jax.Array,          # (S,) int32 cursors (key at ``pos`` is newest)
    *,
    interpret: bool = False,
) -> jax.Array:
    """Flash decode over each slot's pages, several pages a grid step.

    The grid is ``(S, Hk / heads, n_blocks)``: a step serves one slot, a
    tile of ``heads`` KV heads (all of them unless VMEM forbids) and a
    compute block of ``pages`` table entries, both from
    :func:`decode_schedule`.  Each of the block's pages is its own K and
    V operand, a ``(1, heads, bs, d)`` block of the pool whose index the
    scalar-prefetched :func:`_fetch_table` resolves, so the pipeline
    copies the next step's pages while this one computes.  Only pages at
    or before the slot's cursor are fetched: every other operand holds
    its page, and a block wholly past the cursor is a step with no copy
    and no compute.  A freed slot (``pos == 0``) reads its first page.
    """
    S, Hk, G, d = q.shape
    bs = cache_k.shape[2]
    nb = block_tables.shape[1]
    pages, heads = decode_schedule(Hk, bs, d, cache_k.dtype, nb)
    n_cb = pl.cdiv(nb, pages)
    # q.K takes bf16 operands where both hold bf16 values exactly (int8
    # keys do): the products are exact in the f32 accumulation
    qk_dtype = (jnp.bfloat16 if q.dtype == jnp.bfloat16
                and cache_k.dtype in (jnp.bfloat16, jnp.int8)
                else jnp.float32)
    kernel = functools.partial(_decode_kernel, bs=bs, pages=pages,
                               n_blocks=nb, scale=d ** -0.5,
                               qk_dtype=qk_dtype)
    head_spec = pl.BlockSpec((1, heads, G, d),
                             lambda s, h, c, fe, ps: (s, h, 0, 0))

    def page_spec(j):
        return pl.BlockSpec(
            (1, heads, bs, d),
            lambda s, h, c, fe, ps: (fe[(s * n_cb + c) * pages + j], h, 0, 0))

    page_specs = [page_spec(j) for j in range(pages)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(S, Hk // heads, n_cb),
        in_specs=[head_spec] + page_specs + page_specs,
        out_specs=head_spec,
        scratch_shapes=[
            pltpu.VMEM((heads, G, d), jnp.float32),   # output accumulator
            pltpu.VMEM((heads, G, 1), jnp.float32),   # running row max
            pltpu.VMEM((heads, G, 1), jnp.float32),   # running row sum
        ],
    )
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S, Hk, G, d), q.dtype),
        interpret=interpret,
    )(_fetch_table(block_tables, pos, bs, pages), pos, q,
      *[cache_k] * pages, *[cache_v] * pages)


# ---------------------------------------------------------------------------
# chunked prefill: one slot's chunk of C queries at absolute positions
# ---------------------------------------------------------------------------

def _prefill_kernel(bt_ref, span_ref, q_ref, k_ref, v_ref, o_ref,
                    acc_ref, m_ref, l_ref, *, bs: int, n_blocks: int,
                    group: int, scale: float):
    ki = pl.program_id(1)
    start, valid_end = span_ref[0], span_ref[1]

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(ki * bs < valid_end)
    def _compute():
        C = q_ref.shape[0]
        q = q_ref[:, 0].astype(jnp.float32).reshape(C * group, -1)
        k = k_ref[0, 0].astype(jnp.float32)   # (bs, d)
        v = v_ref[0, 0].astype(jnp.float32)
        sc = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32) * scale
        # row r holds query position start + r // group (grouped heads are
        # interleaved row-major); chunk positions are absolute, so a
        # prefix-cached chunk simply starts past the shared blocks
        rows = jax.lax.broadcasted_iota(jnp.int32, sc.shape, 0)
        q_pos = start + rows // group
        k_pos = ki * bs + jax.lax.broadcasted_iota(jnp.int32, sc.shape, 1)
        mask = (k_pos <= q_pos) & (k_pos < valid_end)
        sc = jnp.where(mask, sc, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(sc, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.where(mask, jnp.exp(sc - m_new), 0.0)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ki == n_blocks - 1)
    def _finalize():
        C = o_ref.shape[0]
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[:, 0] = (acc_ref[...] / l).reshape(C, group, -1).astype(
            o_ref.dtype)


def paged_prefill_fwd(
    q: jax.Array,            # (C, Hk, G, d) one prompt chunk of one slot
    cache_k: jax.Array,      # (N, Hk, bs, d) global block pool
    cache_v: jax.Array,      # (N, Hk, bs, d)
    block_table: jax.Array,  # (max_bps,) int32 — the slot's table
    start: jax.Array,        # scalar: absolute position of q[0]
    valid: jax.Array,        # scalar: valid chunk tokens (tail is padding)
    *,
    interpret: bool = False,
) -> jax.Array:
    """See :func:`paged_verify_fwd` for the multi-slot q_len>1 variant."""
    C, Hk, G, d = q.shape
    bs = cache_k.shape[2]
    nb = block_table.shape[0]
    span = jnp.stack([jnp.asarray(start, jnp.int32),
                      jnp.asarray(start + valid, jnp.int32)])
    kernel = functools.partial(_prefill_kernel, bs=bs, n_blocks=nb,
                               group=G, scale=d ** -0.5)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(Hk, nb),
        in_specs=[
            pl.BlockSpec((C, 1, G, d), lambda h, ki, bt, sp: (0, h, 0, 0)),
            pl.BlockSpec((1, 1, bs, d),
                         lambda h, ki, bt, sp: (bt[ki], h, 0, 0)),
            pl.BlockSpec((1, 1, bs, d),
                         lambda h, ki, bt, sp: (bt[ki], h, 0, 0)),
        ],
        out_specs=pl.BlockSpec((C, 1, G, d),
                               lambda h, ki, bt, sp: (0, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((C * G, d), jnp.float32),
            pltpu.VMEM((C * G, 1), jnp.float32),
            pltpu.VMEM((C * G, 1), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((C, Hk, G, d), q.dtype),
        interpret=interpret,
    )(block_table, span, q, cache_k, cache_v)


# ---------------------------------------------------------------------------
# speculative verify: Q = k+1 query tokens for EVERY slot, each slot's
# queries at absolute positions pos[s] .. pos[s]+Q-1 against its own table
# ---------------------------------------------------------------------------

def _verify_kernel(bt_ref, pos_ref, q_ref, k_ref, v_ref, o_ref,
                   acc_ref, m_ref, l_ref, *, bs: int, n_blocks: int,
                   group: int, scale: float):
    s = pl.program_id(0)
    ki = pl.program_id(2)
    pos = pos_ref[s]                     # slot cursor: query i sits at pos+i

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(ki * bs <= pos + q_ref.shape[1] - 1)
    def _compute():
        Q = q_ref.shape[1]
        q = q_ref[0].astype(jnp.float32).reshape(Q * group, -1)
        k = k_ref[0, 0].astype(jnp.float32)   # (bs, d) — int8 KV
        v = v_ref[0, 0].astype(jnp.float32)   # dequantizes right here
        sc = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32) * scale
        # row r holds query position pos + r // group (grouped heads
        # interleaved row-major, as in the prefill kernel); keys past each
        # query's own position — including this step's not-yet-verified
        # draft keys — are masked causally
        rows = jax.lax.broadcasted_iota(jnp.int32, sc.shape, 0)
        q_pos = pos + rows // group
        k_pos = ki * bs + jax.lax.broadcasted_iota(jnp.int32, sc.shape, 1)
        mask = k_pos <= q_pos
        sc = jnp.where(mask, sc, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(sc, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.where(mask, jnp.exp(sc - m_new), 0.0)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ki == n_blocks - 1)
    def _finalize():
        Q = o_ref.shape[1]
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, :, 0] = (acc_ref[...] / l).reshape(Q, group, -1).astype(
            o_ref.dtype)


def paged_verify_fwd(
    q: jax.Array,            # (S, Q, Hk, G, d) Q=k+1 query tokens per slot
    cache_k: jax.Array,      # (N, Hk, bs, d) global block pool
    cache_v: jax.Array,      # (N, Hk, bs, d)
    block_tables: jax.Array,  # (S, max_bps) int32 physical block ids
    pos: jax.Array,          # (S,) int32 cursors (query i is at pos+i)
    *,
    interpret: bool = False,
) -> jax.Array:
    """Batched multi-query pass for speculative verify.

    Merges the decode kernel's per-slot block-table addressing with the
    chunked-prefill kernel's multi-query causal masking: every slot
    attends its Q = k+1 candidate tokens (the pending token plus k draft
    proposals, already scattered into the slot's writable blocks at
    ``pos .. pos+Q-1``) over its own virtual sequence in one dispatch.
    KV blocks entirely past a slot's candidate span are skipped.
    """
    S, Q, Hk, G, d = q.shape
    bs = cache_k.shape[2]
    nb = block_tables.shape[1]
    kernel = functools.partial(_verify_kernel, bs=bs, n_blocks=nb,
                               group=G, scale=d ** -0.5)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(S, Hk, nb),
        in_specs=[
            pl.BlockSpec((1, Q, 1, G, d),
                         lambda s, h, ki, bt, ps: (s, 0, h, 0, 0)),
            pl.BlockSpec((1, 1, bs, d),
                         lambda s, h, ki, bt, ps: (bt[s, ki], h, 0, 0)),
            pl.BlockSpec((1, 1, bs, d),
                         lambda s, h, ki, bt, ps: (bt[s, ki], h, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, Q, 1, G, d),
                               lambda s, h, ki, bt, ps: (s, 0, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((Q * G, d), jnp.float32),
            pltpu.VMEM((Q * G, 1), jnp.float32),
            pltpu.VMEM((Q * G, 1), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S, Q, Hk, G, d), q.dtype),
        interpret=interpret,
    )(block_tables, pos, q, cache_k, cache_v)
