"""Pure-jnp oracles for the paged attention kernels.

These implement the *gather semantics* the engine's XLA path executes:
pages are materialized into a contiguous virtual sequence and attention
runs over it eagerly — the exact data movement the Pallas kernels elide.
Kernel == ref (allclose) therefore proves paged flash == gather.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def _gather_pages(cache, table):
    """(N, Hk, bs, d)[table] -> (L_virt, Hk, d) contiguous virtual page."""
    nb, (Hk, bs, d) = table.shape[0], cache.shape[1:]
    return cache[table].transpose(0, 2, 1, 3).reshape(nb * bs, Hk, d)


def paged_decode_ref(q: jax.Array, cache_k: jax.Array, cache_v: jax.Array,
                     block_tables: jax.Array, pos: jax.Array) -> jax.Array:
    """q: (S, Hk, G, d); caches: (N, Hk, bs, d); tables: (S, nb); pos: (S,).

    Each slot attends its one query token over keys ``[0, pos[s]]`` of its
    gathered virtual sequence.
    """
    S, Hk, G, d = q.shape
    L = block_tables.shape[1] * cache_k.shape[2]
    k_pos = jnp.arange(L, dtype=jnp.int32)

    def one_slot(qs, table, p):
        pk = _gather_pages(cache_k, table).astype(jnp.float32)
        pv = _gather_pages(cache_v, table).astype(jnp.float32)
        sc = jnp.einsum("kgd,lkd->kgl", qs.astype(jnp.float32), pk) * d ** -0.5
        sc = jnp.where((k_pos <= p)[None, None], sc, -1e30)
        pr = jax.nn.softmax(sc, axis=-1)
        return jnp.einsum("kgl,lkd->kgd", pr, pv)

    out = jax.vmap(one_slot)(q, block_tables, pos)
    return out.astype(q.dtype)


def paged_verify_ref(q: jax.Array, cache_k: jax.Array, cache_v: jax.Array,
                     block_tables: jax.Array, pos: jax.Array) -> jax.Array:
    """q: (S, Q, Hk, G, d); caches: (N, Hk, bs, d); tables: (S, nb);
    pos: (S,).

    Speculative verify semantics: slot ``s``'s query ``i`` sits at
    absolute position ``pos[s] + i`` and attends keys ``[0, pos[s] + i]``
    of its gathered virtual sequence (the candidate keys themselves
    included — they were scattered before attention, like a prefill
    chunk's own tokens).
    """
    S, Q, Hk, G, d = q.shape
    L = block_tables.shape[1] * cache_k.shape[2]
    k_pos = jnp.arange(L, dtype=jnp.int32)

    def one_slot(qs, table, p):
        pk = _gather_pages(cache_k, table).astype(jnp.float32)
        pv = _gather_pages(cache_v, table).astype(jnp.float32)
        sc = jnp.einsum("qkgd,lkd->qkgl", qs.astype(jnp.float32),
                        pk) * d ** -0.5
        q_pos = p + jnp.arange(Q, dtype=jnp.int32)
        mask = k_pos[None, :] <= q_pos[:, None]
        sc = jnp.where(mask[:, None, None], sc, -1e30)
        pr = jax.nn.softmax(sc, axis=-1)
        return jnp.einsum("qkgl,lkd->qkgd", pr, pv)

    out = jax.vmap(one_slot)(q, block_tables, pos)
    return out.astype(q.dtype)


def paged_prefill_ref(q: jax.Array, cache_k: jax.Array, cache_v: jax.Array,
                      block_table: jax.Array, start, valid) -> jax.Array:
    """q: (C, Hk, G, d) chunk at absolute positions ``start + [0, C)``;
    keys ``[0, start + valid)`` of the gathered virtual sequence are live
    (causally masked); chunk rows past ``valid`` are padding (garbage out).
    """
    C, Hk, G, d = q.shape
    L = block_table.shape[0] * cache_k.shape[2]
    pk = _gather_pages(cache_k, block_table).astype(jnp.float32)
    pv = _gather_pages(cache_v, block_table).astype(jnp.float32)
    sc = jnp.einsum("skgd,lkd->skgl", q.astype(jnp.float32), pk) * d ** -0.5
    q_pos = start + jnp.arange(C, dtype=jnp.int32)
    k_pos = jnp.arange(L, dtype=jnp.int32)
    mask = ((k_pos[None, :] <= q_pos[:, None])
            & (k_pos[None, :] < start + valid))
    sc = jnp.where(mask[:, None, None], sc, -1e30)
    pr = jax.nn.softmax(sc, axis=-1)
    return jnp.einsum("skgl,lkd->skgd", pr, pv).astype(q.dtype)
