"""Block-paged KV cache for the continuous-batching engine.

KV storage is one global pool of ``n_blocks`` fixed-size blocks of
``block_size`` token positions, in preallocated, sharded cache buffers
shaped

    (n_attn_layers, n_blocks, n_kv_heads, block_size, head_dim)

Head-major within a block, so each (block, head) pair is one contiguous
``(block_size, head_dim)`` tile: the shape a Pallas TPU kernel can DMA
block-by-block through the table (``repro.kernels.paged_attention``).

Each of ``max_slots`` concurrent requests owns a *block table* — a row of
physical block ids whose concatenation is the request's virtual KV
sequence (attention gathers pages through the table) — plus a write
cursor ``pos`` (tokens cached for that slot, prefix hits included).
Blocks are ref-counted by the host-side :class:`~.block_pool.BlockPool`,
so requests whose prompts share a block-aligned prefix map the same
physical blocks (radix prefix caching, copy-on-write on divergence);
see ``repro.engine.block_pool``.

Supports quantized KV storage (``int8`` buffers, paper §3.3.3) —
attention math reads the cache cast back to the activation dtype.

Migration note (PR 3): the former slot-paged ``PagedKVCache(cfg,
max_slots, max_len)`` — one contiguous ``max_len`` page per slot — was
replaced by :class:`BlockPagedKVCache`.  ``PagedKVCache`` remains as a
constructor-compatible alias that maps the old geometry onto blocks
(``n_blocks = max_slots * ceil(max_len / block_size)``).
"""
from __future__ import annotations

import dataclasses
from typing import Dict

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding

from repro.configs.base import ArchConfig
from repro.runtime import sharding as S

from .sampling import kv_jnp_dtype


def engine_supported(cfg: ArchConfig) -> bool:
    """Engine v1 serves homogeneous full-attention stacks (GQA/MHA/MQA).

    SSM / RG-LRU hybrids, MLA latent caches, local-window ring buffers and
    encoder-decoder cross caches keep using the legacy lockstep
    ``repro.runtime.serve.Server`` path.
    """
    return (all(k == "attn" for k in cfg.block_kinds())
            and cfg.mla is None
            and not cfg.local_window
            and not cfg.n_encoder_layers)


def check_supported(cfg: ArchConfig) -> None:
    if not engine_supported(cfg):
        raise ValueError(
            f"engine does not support arch {cfg.name!r} "
            f"(family={cfg.family}, mla={cfg.mla is not None}, "
            f"local_window={cfg.local_window}); use repro.runtime.Server")


@dataclasses.dataclass(frozen=True)
class BlockPagedKVCache:
    """Geometry + (de)allocation of the block-paged cache buffers.

    The buffers themselves live inside the engine's device state dict (so
    they can be donated through jit); this object is the static descriptor
    that creates, shards and interprets them.  ``max_blocks_per_seq`` is
    the block-table width — the per-request virtual KV capacity is
    ``max_blocks_per_seq * block_size`` positions.
    """
    cfg: ArchConfig
    max_slots: int
    n_blocks: int
    block_size: int
    max_blocks_per_seq: int
    kv_dtype: str = "bf16"
    # multi-tenant LoRA geometry: when lora_slots > 0 the state carries a
    # device adapter pool — stacked rank-padded A/B factors for the four
    # attention projections of every layer (see repro.engine.adapter_pool)
    # — plus a per-request adapter pool-slot index (-1 = base model).
    lora_slots: int = 0
    lora_max_rank: int = 0
    lora_dtype: str = "bf16"

    def __post_init__(self):
        check_supported(self.cfg)
        if min(self.max_slots, self.n_blocks, self.block_size,
               self.max_blocks_per_seq) < 1:
            raise ValueError("cache geometry fields must all be >= 1")
        if self.lora_slots > 0 and self.lora_max_rank < 1:
            raise ValueError("lora_slots > 0 requires lora_max_rank >= 1")

    @property
    def n_layers(self) -> int:
        return self.cfg.n_layers

    @property
    def max_len(self) -> int:
        """Virtual KV positions addressable by one request's table."""
        return self.max_blocks_per_seq * self.block_size

    def buffer_shape(self):
        c = self.cfg
        return (c.n_layers, self.n_blocks, c.n_kv_heads,
                self.block_size, c.head_dim)

    def init_state(self) -> Dict[str, jax.Array]:
        """Fresh engine device state: empty block pool + per-slot tables."""
        kvd = kv_jnp_dtype(self.kv_dtype)
        shape = self.buffer_shape()
        state = {
            "cache_k": jnp.zeros(shape, kvd),
            "cache_v": jnp.zeros(shape, kvd),
            # per-slot block table: physical block id of each virtual page
            "block_tables": jnp.zeros(
                (self.max_slots, self.max_blocks_per_seq), jnp.int32),
            # per-slot number of cached tokens (the slot's write cursor;
            # counts prefix-hit tokens mapped from shared blocks too)
            "pos": jnp.zeros((self.max_slots,), jnp.int32),
            # last sampled token per slot (input to the next decode step)
            "tok": jnp.zeros((self.max_slots,), jnp.int32),
        }
        if self.lora_slots > 0:
            state.update(self._lora_buffers())
            # adapter pool slot serving each engine slot (-1 = base model)
            state["adapter_slots"] = jnp.full(
                (self.max_slots,), -1, jnp.int32)
        return state

    def _lora_buffers(self) -> Dict[str, jax.Array]:
        """Device adapter pool: (L, lora_slots, k_p, R) / (L, lora_slots,
        R, n_p) per projection, rank-padded to ``lora_max_rank``."""
        c = self.cfg
        ld = kv_jnp_dtype(self.lora_dtype)
        L, P, R = c.n_layers, self.lora_slots, self.lora_max_rank
        d, H, Hk, hd = c.d_model, c.n_heads, c.n_kv_heads, c.head_dim
        dims = {"q": (d, H * hd), "k": (d, Hk * hd), "v": (d, Hk * hd),
                "o": (H * hd, d)}
        out = {}
        for name, (k, n) in dims.items():
            out[f"lora_A_{name}"] = jnp.zeros((L, P, k, R), ld)
            out[f"lora_B_{name}"] = jnp.zeros((L, P, R, n), ld)
        return out

    def abstract_state(self) -> Dict[str, jax.ShapeDtypeStruct]:
        return jax.eval_shape(self.init_state)

    def logical_axes(self) -> Dict[str, tuple]:
        # the block axis is a global pool any slot may address, so it is
        # replicated; TP shards the KV-head axis — each chip of a
        # ``model=tp`` mesh owns ``n_kv_heads/tp`` heads of EVERY block
        # (the paged Pallas path shard_maps over the same axis).  The
        # layer axis shards over the ``pipe`` axis when the mesh has one
        # (each pipeline stage owns its layers' blocks, composing with
        # the kv_heads split); on a pipe-less mesh it stays replicated.
        # No kv_len fallback here: intra-block token sharding would split
        # scatter targets across chips for zero capacity win.
        axes = {
            "cache_k": ("layers", None, "kv_heads", None, None),
            "cache_v": ("layers", None, "kv_heads", None, None),
            "block_tables": ("batch", None),
            "pos": ("batch",),
            "tok": ("batch",),
        }
        if self.lora_slots > 0:
            # adapter pool buffers stay replicated under GSPMD: on the
            # paged path the grouped-LoRA Pallas kernel is shard_map'd
            # over the rank axis explicitly (ops.make_sharded_grouped_lora)
            # and on the gather path the factors are small enough that
            # replication beats resharding the per-step gathers.  The
            # layer axis still pipelines.
            for name in ("q", "k", "v", "o"):
                axes[f"lora_A_{name}"] = ("layers", None, None, None)
                axes[f"lora_B_{name}"] = ("layers", None, None, None)
            axes["adapter_slots"] = ("batch",)
        return axes

    def shardings(self, mesh: Mesh, policy: S.ShardingPolicy
                  ) -> Dict[str, NamedSharding]:
        axes = self.logical_axes()
        out = {}
        for k, sds in self.abstract_state().items():
            out[k] = NamedSharding(
                mesh, S.spec_for(axes[k], tuple(sds.shape), mesh, policy))
        return out

    # ------------------------------------------------------------------
    # slot lifecycle (host-side, between jitted engine steps)
    # ------------------------------------------------------------------
    def reset_slot(self, state: Dict[str, jax.Array], slot: int
                   ) -> Dict[str, jax.Array]:
        """Clear a slot's cursor for reuse.  O(1): stale KV entries are
        unreachable once ``pos == 0`` (block frees happen in the pool)."""
        state = dict(state)
        state["pos"] = state["pos"].at[slot].set(0)
        state["tok"] = state["tok"].at[slot].set(0)
        if "adapter_slots" in state:
            state["adapter_slots"] = state["adapter_slots"].at[slot].set(-1)
        return state

    def copy_block(self, state: Dict[str, jax.Array], src: int, dst: int
                   ) -> Dict[str, jax.Array]:
        """Copy-on-write fork: duplicate physical block ``src`` into the
        freshly allocated ``dst`` across all layers and both K/V buffers,
        so the owner of ``dst`` may write without dirtying the shared
        ``src``."""
        state = dict(state)
        for c in ("cache_k", "cache_v"):
            state[c] = state[c].at[:, dst].set(state[c][:, src])
        return state

    def bytes_per_block(self) -> int:
        c = self.cfg
        el = jnp.dtype(kv_jnp_dtype(self.kv_dtype)).itemsize
        return (2 * c.n_layers * self.block_size * c.n_kv_heads
                * c.head_dim * el)

    def total_bytes(self) -> int:
        return self.n_blocks * self.bytes_per_block()


def PagedKVCache(cfg: ArchConfig, max_slots: int, max_len: int,
                 kv_dtype: str = "bf16", *,
                 block_size: int = 16) -> BlockPagedKVCache:
    """Deprecated alias for the pre-block-paging constructor signature.

    Maps the old slot-paged geometry (one ``max_len`` page per slot) onto
    an equivalently sized block pool.  New code should construct
    :class:`BlockPagedKVCache` directly.
    """
    bps = -(-max_len // block_size)
    return BlockPagedKVCache(cfg, max_slots, n_blocks=max_slots * bps,
                             block_size=block_size, max_blocks_per_seq=bps,
                             kv_dtype=kv_dtype)
