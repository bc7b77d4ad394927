"""Jit'd public wrappers for the paged attention kernels.

On TPU these lower the Pallas kernels; on CPU (this container) they run
the kernel bodies in interpret mode so correctness holds everywhere.  The
wrappers are what ``repro.engine.decode_loop`` calls when the engine is
configured with ``attn_impl="paged"``.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax

from .paged_attention import (paged_decode_fwd, paged_prefill_fwd,
                              paged_verify_fwd)
from .ref import paged_decode_ref, paged_prefill_ref, paged_verify_ref


def _on_cpu() -> bool:
    return jax.default_backend() == "cpu"


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_decode(q: jax.Array, cache_k: jax.Array, cache_v: jax.Array,
                 block_tables: jax.Array, pos: jax.Array, *,
                 interpret: Optional[bool] = None) -> jax.Array:
    """Paged flash decode: one query token per slot against its table.

    q: (S, Hk, G, d); caches: (N, Hk, bs, d); tables: (S, max_bps) int32;
    pos: (S,) cursors — the key at ``pos[s]`` is the newest attended.
    """
    if interpret is None:
        interpret = _on_cpu()
    return paged_decode_fwd(q, cache_k, cache_v, block_tables, pos,
                            interpret=interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_prefill(q: jax.Array, cache_k: jax.Array, cache_v: jax.Array,
                  block_table: jax.Array, start: jax.Array,
                  valid: jax.Array, *,
                  interpret: Optional[bool] = None) -> jax.Array:
    """Paged chunked prefill: one slot's chunk at absolute positions.

    q: (C, Hk, G, d); ``start`` is the absolute position of q[0] (cached
    prefix included), ``valid`` the live chunk tokens (the tail is padding).
    """
    if interpret is None:
        interpret = _on_cpu()
    return paged_prefill_fwd(q, cache_k, cache_v, block_table, start, valid,
                             interpret=interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_verify(q: jax.Array, cache_k: jax.Array, cache_v: jax.Array,
                 block_tables: jax.Array, pos: jax.Array, *,
                 interpret: Optional[bool] = None) -> jax.Array:
    """Paged flash speculative verify: Q candidate tokens per slot.

    q: (S, Q, Hk, G, d) — slot ``s``'s queries sit at absolute positions
    ``pos[s] .. pos[s]+Q-1`` (the pending token plus k=Q-1 drafts, whose
    K/V were scattered before this call); caches: (N, Hk, bs, d);
    tables: (S, max_bps) int32; pos: (S,) cursors.
    """
    if interpret is None:
        interpret = _on_cpu()
    return paged_verify_fwd(q, cache_k, cache_v, block_tables, pos,
                            interpret=interpret)


__all__ = ["paged_decode", "paged_prefill", "paged_verify",
           "paged_decode_ref", "paged_prefill_ref", "paged_verify_ref"]
