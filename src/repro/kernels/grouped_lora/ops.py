"""Jit'd public wrappers for the grouped-LoRA kernel.

On TPU these lower the Pallas kernel; on CPU (this container) they run
the kernel body in interpret mode so correctness holds everywhere.
``repro.engine.decode_loop`` calls :func:`grouped_lora` on the
``paged`` attention path (the Pallas-kernel engine configuration) and
the gather reference on the ``gather`` path.

Tensor parallelism: Pallas calls are opaque to GSPMD, so
:func:`make_sharded_grouped_lora` shard_maps the kernel over the rank
axis — A column-partitioned ``(P, k, R/tp)``, B row-partitioned
``(P, R/tp, n)``, activations and indices replicated — and ``psum``-s
the per-chip partial deltas (a sum over disjoint rank lanes, so the
math is the unsharded contraction reassociated).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
from jax.sharding import Mesh, PartitionSpec as P

from .grouped_lora import grouped_lora_fwd
from .ref import grouped_lora_pregathered, grouped_lora_ref


def _on_cpu() -> bool:
    return jax.default_backend() == "cpu"


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def grouped_lora(x: jax.Array, A: jax.Array, B: jax.Array, idx: jax.Array,
                 *, scale: float = 1.0,
                 interpret: Optional[bool] = None) -> jax.Array:
    """Grouped low-rank delta ``scale·(x @ A[idx]) @ B[idx]``.

    x: (S, T, k); A: (P, k, R); B: (P, R, n); idx: (S,) int32 pool slots
    (-1 = no adapter → exact-zero delta).  Returns (S, T, n) in x.dtype.
    """
    if interpret is None:
        interpret = _on_cpu()
    return grouped_lora_fwd(x, A, B, idx, scale=scale, interpret=interpret)


def make_sharded_grouped_lora(mesh: Mesh, tp_axis: str, *,
                              scale: float = 1.0):
    """shard_map'd grouped-LoRA over the rank axis of a ``tp`` mesh.

    Each chip runs the kernel on its ``R/tp`` rank lanes of every pooled
    adapter (A columns / B rows) and the partial deltas are ``psum``-med
    — requires the padded pool rank to be divisible by the axis size.
    """
    def _local(x, A, B, idx):
        part = grouped_lora(x, A, B, idx, scale=scale)
        return jax.lax.psum(part, tp_axis)

    return jax.shard_map(
        _local, mesh=mesh,
        in_specs=(P(None, None, None), P(None, None, tp_axis),
                  P(None, tp_axis, None), P(None)),
        out_specs=P(None, None, None), check_vma=False)


__all__ = ["grouped_lora", "grouped_lora_pregathered", "grouped_lora_ref",
           "make_sharded_grouped_lora"]
