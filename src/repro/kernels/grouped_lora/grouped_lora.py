"""Grouped/ragged low-rank (LoRA) matmul Pallas TPU kernel.

Multi-tenant serving applies a *different* adapter per decode slot:
slot ``s`` carrying adapter ``idx[s]`` needs

    delta[s] = scale · (x[s] @ A[idx[s]]) @ B[idx[s]]

for the whole mixed batch in one fused pass — the grouped analogue of
the per-GEMM LoRA cost the paper prices (§3.3.5 Eq. 7).  The naive
alternatives both lose: looping tenants serializes the batch, and
gathering ``A[idx]``/``B[idx]`` into per-slot copies rematerializes
adapter weights in HBM per layer per step (the same data-movement sin
the paged-attention gather path commits with KV pages).

Kernel shape (mirrors ``repro.kernels.paged_attention``):

* the per-slot adapter indices are a *scalar-prefetch* operand, so the
  A/B BlockSpec index maps resolve ``idx[s]`` to a physical pool slot
  before the DMA is issued — each grid step streams exactly one
  adapter's factors into VMEM, never a gathered copy;
* ``idx[s] < 0`` means "no adapter": the block maps clamp to pool slot
  0 (some valid DMA must happen) and ``pl.when`` skips the MXU work,
  writing a zero delta;
* mixed ranks ride as *rank buckets by zero padding*: every adapter is
  stored padded to the pool-wide ``R = max rank`` with ``A[:, r:] = 0``
  and ``B[r:, :] = 0``, so a rank-``r`` adapter's padded lanes
  contribute exact zeros — raggedness costs pad-lane MXU throughput,
  never correctness;
* the two dots accumulate in f32 (``preferred_element_type``) and cast
  back to the activation dtype on the way out;
* the output columns stream in lane tiles (a second grid axis): ``x @ A``
  is computed once per slot into VMEM scratch and each tile of ``B`` is
  applied to it, so VMEM holds a ``(T, tile)`` output block rather than
  the whole ``(T, n)`` row — an MLP-width ``n`` at a prefill-chunk ``T``
  would otherwise overrun the TPU's scoped VMEM.

Tensor parallelism shards the *rank* axis: A column- and B
row-partitioned (see ``ops.make_sharded_grouped_lora``), each chip
computing a partial delta over its ``R/tp`` rank lanes, summed with one
``psum`` — low-rank factors are small enough that replicating the
activations costs nothing.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _n_tile(n: int) -> int:
    """Output-column tile: the largest of 512/256/128 lanes dividing
    ``n`` (else all of ``n``), so a ``(T, n)`` output of any projection
    width streams through VMEM a tile at a time."""
    return next((t for t in (512, 256, 128) if n % t == 0), n)


def _grouped_lora_kernel(idx_ref, x_ref, a_ref, b_ref, o_ref, xa_ref, *,
                         scale: float):
    s = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when((idx_ref[s] >= 0) & (j == 0))
    def _down():
        # x @ A once per slot; every output-column tile reuses it
        x = x_ref[0].astype(jnp.float32)           # (T, k)
        a = a_ref[0].astype(jnp.float32)           # (k, R) — padded rank
        xa_ref[...] = jax.lax.dot_general(
            x, a, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(idx_ref[s] >= 0)
    def _up():
        b = b_ref[0].astype(jnp.float32)           # (R, tn)
        o = jax.lax.dot_general(xa_ref[...], b, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
        o_ref[0] = (scale * o).astype(o_ref.dtype)

    @pl.when(idx_ref[s] < 0)
    def _skip():
        o_ref[0] = jnp.zeros(o_ref.shape[1:], o_ref.dtype)


def grouped_lora_fwd(
    x: jax.Array,        # (S, T, k) per-slot activations (T query tokens)
    A: jax.Array,        # (P, k, R) adapter pool, rank-padded A factors
    B: jax.Array,        # (P, R, n) adapter pool, rank-padded B factors
    idx: jax.Array,      # (S,) int32 pool slot per batch slot (-1 = none)
    *,
    scale: float = 1.0,
    interpret: bool = False,
) -> jax.Array:
    """Fused grouped low-rank delta ``scale·(x @ A[idx]) @ B[idx]``.

    Returns the (S, T, n) delta in ``x.dtype``; the caller adds it onto
    the base projection.  Slots with ``idx < 0`` get an exact zero.
    """
    S, T, k = x.shape
    P, k2, R = A.shape
    P2, R2, n = B.shape
    if k2 != k or P2 != P or R2 != R:
        raise ValueError(f"inconsistent grouped-LoRA operands: x {x.shape}, "
                         f"A {A.shape}, B {B.shape}")
    tn = _n_tile(n)
    kernel = functools.partial(_grouped_lora_kernel, scale=scale)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(S, n // tn),
        in_specs=[
            pl.BlockSpec((1, T, k), lambda s, j, idx_ref: (s, 0, 0)),
            # clamp -1 ("no adapter") to slot 0: the DMA must target a
            # real block; the kernel body skips the compute either way
            pl.BlockSpec((1, k, R),
                         lambda s, j, idx_ref: (jnp.maximum(idx_ref[s], 0),
                                                0, 0)),
            pl.BlockSpec((1, R, tn),
                         lambda s, j, idx_ref: (jnp.maximum(idx_ref[s], 0),
                                                0, j)),
        ],
        out_specs=pl.BlockSpec((1, T, tn), lambda s, j, idx_ref: (s, 0, j)),
        scratch_shapes=[pltpu.VMEM((T, R), jnp.float32)],   # x @ A
    )
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S, T, n), x.dtype),
        interpret=interpret,
    )(idx, x, A, B)
