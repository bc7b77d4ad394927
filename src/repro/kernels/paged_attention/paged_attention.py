"""Paged flash attention Pallas TPU kernels (decode + chunked prefill).

The serving engine stores KV in a global head-major block pool
``(N, Hk, bs, hd)`` addressed through per-slot block tables.  The XLA
engine path gathers each
slot's blocks back into a contiguous ``(L_virt, Hk, hd)`` page buffer per
layer per step — exactly the HBM materialization the paper's canonical
fusion example (flash attention, §3.2.1) exists to elide.  These kernels
read K/V *block-by-block through the block table* with online softmax:

* the block table (and per-slot cursors) are scalar-prefetch operands, so
  the KV BlockSpec index map resolves ``table[s, i]`` to a physical block
  id before the DMA is issued — no page buffer ever exists in HBM;
* GQA is native: the grid iterates KV heads and each step processes that
  head's whole query group, so repeated KV is never materialized;
* KV blocks past the slot's cursor are skipped with ``pl.when`` (zero MXU
  work — the gather path pays for the full virtual width);
* int8 KV dequantizes in-kernel (``astype`` on the VMEM-resident block),
  matching the engine's cast-based KV compression (§3.3.3).

The pool is head-major so that one grid step's K/V block is
``(1, 1, bs, hd)``: its last two dimensions are the whole ``(bs, hd)``
tile of the array, which Mosaic accepts for any ``bs``.  A token-major
pool ``(N, bs, Hk, hd)`` would need a ``(1, bs, 1, hd)`` block, whose
second-minor dimension (one of ``Hk`` heads) the TPU compiler refuses.

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

def _decode_kernel(bt_ref, pos_ref, q_ref, k_ref, v_ref, o_ref,
                   acc_ref, m_ref, l_ref, *, bs: int, n_blocks: int,
                   scale: float):
    s = pl.program_id(0)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    pos = pos_ref[s]                               # slot cursor: key at
                                                   # ``pos`` was just written
    @pl.when(ki * bs <= pos)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)        # (G, d)
        k = k_ref[0, 0].astype(jnp.float32)   # (bs, d) — int8 KV
        v = v_ref[0, 0].astype(jnp.float32)   # dequantizes right here
        sc = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32) * scale
        k_pos = ki * bs + jax.lax.broadcasted_iota(jnp.int32, sc.shape, 1)
        mask = k_pos <= pos
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
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


def paged_decode_fwd(
    q: jax.Array,            # (S, Hk, G, d) one query token per slot
    cache_k: jax.Array,      # (N, Hk, bs, d) global block pool
    cache_v: jax.Array,      # (N, Hk, bs, d)
    block_tables: jax.Array,  # (S, max_bps) int32 physical block ids
    pos: jax.Array,          # (S,) int32 cursors (key at ``pos`` is newest)
    *,
    interpret: bool = False,
) -> jax.Array:
    S, Hk, G, d = q.shape
    bs = cache_k.shape[2]
    nb = block_tables.shape[1]
    kernel = functools.partial(_decode_kernel, bs=bs, n_blocks=nb,
                               scale=d ** -0.5)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(S, Hk, nb),
        in_specs=[
            pl.BlockSpec((1, 1, G, d),
                         lambda s, h, ki, bt, ps: (s, h, 0, 0)),
            pl.BlockSpec((1, 1, bs, d),
                         lambda s, h, ki, bt, ps: (bt[s, ki], h, 0, 0)),
            pl.BlockSpec((1, 1, bs, d),
                         lambda s, h, ki, bt, ps: (bt[s, ki], h, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, G, d),
                               lambda s, h, ki, bt, ps: (s, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((G, d), jnp.float32),    # output accumulator
            pltpu.VMEM((G, 1), jnp.float32),    # running row max
            pltpu.VMEM((G, 1), jnp.float32),    # running row sum
        ],
    )
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S, Hk, G, d), q.dtype),
        interpret=interpret,
    )(block_tables, pos, q, cache_k, cache_v)


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
