"""Fused engine steps: chunked-prefill admission + multi-token decode scan,
gathering attention over block tables (block-paged KV cache).

Replaces the per-token Python dispatch of the legacy ``Server.generate``
loop with two jitted entry points:

* ``prefill_chunk``  — admit one prompt chunk of one request into its
  block table (paper §3.3.4 chunked prefill).  Chunk token positions are
  absolute, so a prefix-cached request simply starts its chunks at
  ``cached_len`` — the shared blocks already hold the prefix K/V and the
  causal mask admits them like any other past tokens.
* ``decode_block``   — ``jax.lax.scan`` over ``decode_block`` tokens for
  *all* slots at once: embedding → layer stack → LM head → sampling all
  inside one jit, with active-slot masking so slots that finish (EOS /
  budget) mid-block stop writing KV and stop advancing, while fresh slots
  keep decoding.  One dispatch per block instead of one per token.

KV reads/writes address physical storage through each slot's block table:
a token at absolute position ``p`` lives in physical block
``table[p // block_size]`` at offset ``p % block_size``, and attention
gathers the table's blocks back into the slot's contiguous virtual
sequence.  Writable blocks are exclusively owned (shared blocks are full
and immutable — the scheduler copy-on-writes before any divergence), so
scatter indices never collide across active slots.

Both operate on the state dict created by ``BlockPagedKVCache.init_state``
and donate it, so cache blocks are updated in place across engine steps.

Two attention read paths (``EngineConfig.attn_impl``):

* ``"gather"`` — XLA reference: gather the table's blocks back into the
  slot's contiguous ``(L_virt, Hk, hd)`` virtual sequence and attend
  eagerly.  Simple, but rematerializes the whole KV span in HBM per layer
  per step — the data movement the paper's fusion example (§3.2.1) elides.
* ``"paged"``  — Pallas paged flash kernels
  (``repro.kernels.paged_attention``): K/V read block-by-block through the
  block table with online softmax, no page buffer, blocks past the cursor
  skipped, int8 KV dequantized in-kernel.  On the CPU backend the kernels
  run in Pallas interpret mode (correct but slow); on TPU they lower to
  Mosaic.

Tensor parallelism: on a mesh whose ``policy.tp_axis`` has size ``tp > 1``
the engine runs sharded over KV heads — weights and the block-paged KV
pool partition per the named shardings (``BlockPagedKVCache.logical_axes``
/ ``param_shardings``), the gather path's attention partitions under
GSPMD, and the Pallas kernels are ``shard_map``-ped over the head axis
(each chip runs the kernel on its ``n_kv_heads/tp`` heads of every
block).  Attention is embarrassingly parallel over GQA head groups, so
the only cross-chip traffic is the all-reduce XLA inserts after the
row-sharded o_proj/down_proj einsums — exactly the collectives the
analytical side prices (``WorkloadModel`` with a ``ShardingPlan``).

Pipeline parallelism: on a mesh with a ``pipe`` axis of size ``pp > 1``
the stacked layer scan splits into ``pp`` contiguous segments
(``_staged_scan``), each aligned with the ``pipe`` sharding of the
stacked params and the KV pool's layer axis — stage ``s`` executes its
layers against its own weight/cache shards and only the carried
activation crosses stages (the hop the analytical side prices as
``wire_bytes``).  The op sequence is identical to the single scan, so
tokens are bit-identical to ``pp=1`` for both attention impls; ``tp``
composes (KV heads × layer stages partition the pool in both axes).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.models import attention as A
from repro.models import blocks as B
from repro.models.layers import apply_norm
from repro.models.model import _lm_head
from repro.runtime import sharding as S

from repro.core.workload import ENGINE_ATTN_IMPLS
from repro.kernels.paged_attention import ops as paged_ops
from repro.kernels.grouped_lora import ops as lora_ops

from .adapter_pool import LORA_FACTORS
from .kv_cache import BlockPagedKVCache
from .sampling import sample

#: the engine always runs exactly one impl (the analytical side's extra
#: ``None`` means "price neither")
ATTN_IMPLS = tuple(i for i in ENGINE_ATTN_IMPLS if i is not None)


def _check_pp(cfg: ArchConfig, pp: int) -> None:
    if pp > 1 and cfg.n_layers % pp:
        raise ValueError(
            f"pipeline-parallel engine splits the layer scan into stages: "
            f"pp={pp} must divide n_layers={cfg.n_layers} of arch "
            f"{cfg.name!r}")


def _check_impl_and_plan(cfg: ArchConfig, mesh: Mesh,
                         policy: S.ShardingPolicy, attn_impl: str):
    """Shared admission check for every jitted-entry-point factory.

    Validates ``attn_impl`` against the engine's vocabulary and the mesh
    plan against the arch (tp must divide both head counts, pp must
    divide the layer stack) and returns ``(tp, pp)``.  All three
    factories go through here so a bad plan fails identically no matter
    which entry point is built first.
    """
    if attn_impl not in ATTN_IMPLS:
        raise ValueError(f"attn_impl must be one of {ATTN_IMPLS}, "
                         f"got {attn_impl!r}")
    tp = S.tp_degree(mesh, policy)
    if tp > 1 and (cfg.n_kv_heads % tp or cfg.n_heads % tp):
        raise ValueError(
            f"tensor-parallel engine shards attention over KV heads: tp={tp}"
            f" must divide n_heads={cfg.n_heads} and "
            f"n_kv_heads={cfg.n_kv_heads} of arch {cfg.name!r}")
    pp = S.pp_degree(mesh, policy)
    _check_pp(cfg, pp)
    return tp, pp


def _make_lora_fn(cache: BlockPagedKVCache, mesh: Mesh,
                  policy: S.ShardingPolicy, attn_impl: str, tp: int):
    """Grouped-LoRA delta callable for this engine configuration, or None.

    Matches the attention dispatch: the ``gather`` path uses the XLA
    gather reference (GSPMD shards it like any einsum); the ``paged``
    path uses the fused Pallas kernel — shard_map'd over the rank axis
    when tp > 1, since Pallas calls are opaque to GSPMD.
    """
    if cache.lora_slots <= 0:
        return None
    if attn_impl == "paged":
        if tp > 1:
            if cache.lora_max_rank % tp:
                raise ValueError(
                    f"tensor-parallel grouped LoRA shards the rank axis: "
                    f"tp={tp} must divide the padded pool rank "
                    f"{cache.lora_max_rank}")
            return lora_ops.make_sharded_grouped_lora(mesh, policy.tp_axis)
        return lora_ops.grouped_lora
    return lora_ops.grouped_lora_ref


def _lora_state_xs(state):
    """Per-layer adapter-pool scan operands (stacked on the layer axis)."""
    return {k: state["lora_" + k] for k in LORA_FACTORS}


def _pregather_lora(xs, idx):
    """Hoist the pool gather out of the step/layer loops (XLA path).

    ``(L, P, ...)`` pool buffers → ``(L, S, ...)`` per-slot factors with
    hole slots (idx < 0) zeroed, so each per-step delta is the two pure
    einsums of ``grouped_lora_pregathered`` instead of gather+mask per
    projection per layer per token: the takes/wheres run once per
    dispatch rather than ``decode_block × n_layers`` times.  Executed
    matmul FLOPs — and therefore the token stream and the audit
    reconciliation — are unchanged.
    """
    safe = jnp.maximum(idx, 0)
    live = (idx >= 0)[None, :, None, None]
    return {k: jnp.where(live, jnp.take(v, safe, axis=1),
                         jnp.zeros((), v.dtype))
            for k, v in xs.items()}


def _qkv_deltas(cfg: ArchConfig, h, lora, lora_idx, lora_fn):
    """Grouped low-rank q/k/v deltas of the normed input, shaped for
    ``_project_qkv(deltas=...)`` (pre-RoPE, pre-GQA-reshape)."""
    b, s, _ = h.shape
    H, Hk, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    dq = lora_fn(h, lora["A_q"], lora["B_q"], lora_idx).reshape(b, s, H, hd)
    dk = lora_fn(h, lora["A_k"], lora["B_k"], lora_idx).reshape(b, s, Hk, hd)
    dv = lora_fn(h, lora["A_v"], lora["B_v"], lora_idx).reshape(b, s, Hk, hd)
    return dq, dk, dv


def _staged_scan(scan_fn, x, xs, pp: int):
    """``jax.lax.scan`` over stacked per-layer leaves, split into ``pp``
    pipeline-stage segments.

    ``pp == 1`` is the literal single ``lax.scan`` of the unstaged engine
    (same HLO, bit-for-bit).  ``pp > 1`` runs one scan per contiguous
    layer segment — the op sequence (and therefore every token) is
    identical, but each segment's params/KV slices align with the
    ``pipe``-axis sharding of the stacked leaves, so under GSPMD stage
    ``s``'s layers execute against stage ``s``'s weight and cache shards
    and the carried activation ``x`` is what moves between stages (the
    hop the analytical side prices as ``wire_bytes``).  Stacked scan
    outputs are concatenated back in layer order.
    """
    if pp <= 1:
        return jax.lax.scan(scan_fn, x, xs)
    L = jax.tree_util.tree_leaves(xs)[0].shape[0]
    seg = L // pp
    outs = []
    for s in range(pp):
        sl = jax.tree_util.tree_map(
            lambda t: jax.lax.slice_in_dim(t, s * seg, (s + 1) * seg,
                                           axis=0), xs)
        x, out = jax.lax.scan(scan_fn, x, sl)
        outs.append(out)
    stacked = jax.tree_util.tree_map(
        lambda *ts: jnp.concatenate(ts, axis=0), *outs)
    return x, stacked


# ---------------------------------------------------------------------------
# per-layer bodies against one block table / all block tables
# ---------------------------------------------------------------------------

def _pages(c, bt):
    """Gather-path page read: ``(N, Hk, bs, hd)`` pool rows of the block
    table(s) ``bt (..., nb)`` → ``(..., nb * bs, Hk, hd)`` contiguous
    virtual sequence(s), token-major like the attention einsums expect."""
    g = jnp.swapaxes(c[bt], -3, -2)             # (..., nb, bs, Hk, hd)
    return g.reshape(*bt.shape[:-1], -1, *g.shape[-2:])


def _channel_mix(cfg: ArchConfig, p, x):
    if "mlp" not in p:
        return x
    h = apply_norm(cfg.norm_kind, x, p["ln2"])
    if cfg.family == "moe":
        y, _ = B.moe_forward(cfg, p["mlp"], h)
    else:
        y = B.mlp_forward(cfg, p["mlp"], h)
    return x + y


def _prefill_layer(cfg: ArchConfig, p, x, ck, cv, bt_slot, pos_q, valid_end,
                   attn_impl: str = "gather",
                   paged_fn=paged_ops.paged_prefill,
                   lora=None, lora_idx=None, lora_fn=None):
    """One layer of a single-slot prompt chunk.

    x: (1, C, d); ck/cv: (N, Hk, bs, hd) full block-pool buffers of this
    layer; bt_slot: (max_bps,) the slot's block table; pos_q: (C,)
    absolute positions of the chunk tokens; positions ``>= valid_end`` are
    padding (their K/V scatter targets block id N — out of bounds, so the
    writes are dropped — and their outputs are ignored by the caller).

    ``lora`` (this layer's adapter-pool factors), ``lora_idx`` (the
    slot's adapter pool index, (1,), -1 = base model) and ``lora_fn``
    add grouped low-rank deltas on q/k/v (pre-RoPE) and the attention
    output — multi-tenant LoRA serving.
    """
    N, bs = ck.shape[0], ck.shape[2]
    L_virt = bt_slot.shape[0] * bs
    h = apply_norm(cfg.norm_kind, x, p["ln1"])
    deltas = (None if lora is None
              else _qkv_deltas(cfg, h, lora, lora_idx, lora_fn))
    q, k_new, v_new = A._project_qkv(cfg, p["attn"], h, pos_q[None, :],
                                     deltas)
    # scatter the chunk's K/V through the block table
    blk = jnp.where(pos_q < valid_end, bt_slot[pos_q // bs], N)
    off = pos_q % bs
    ck = ck.at[blk, :, off].set(k_new[0].astype(ck.dtype))
    cv = cv.at[blk, :, off].set(v_new[0].astype(cv.dtype))
    b, s = x.shape[0], x.shape[1]
    if attn_impl == "paged":
        # read K/V block-by-block through the table — no page buffer
        out = paged_fn(q[0], ck, cv, bt_slot, pos_q[0],
                       valid_end - pos_q[0])
        out = out.reshape(1, s, -1)
    else:
        # gather the slot's pages back into its contiguous virtual sequence
        page_k = _pages(ck, bt_slot[None])
        page_v = _pages(cv, bt_slot[None])
        k_pos = jnp.arange(L_virt, dtype=jnp.int32)
        mask = ((k_pos[None, :] <= pos_q[:, None])
                & (k_pos[None, :] < valid_end))[None, None, None]
        out = A._gqa_scores_softmax_out(q, page_k.astype(x.dtype),
                                        page_v.astype(x.dtype), mask,
                                        cfg.head_dim ** -0.5)
    out_flat = out.reshape(b, s, -1)
    y = jnp.einsum("bshd,hde->bse",
                   out_flat.reshape(b, s, cfg.n_heads, cfg.head_dim),
                   p["attn"]["wo"])
    if lora is not None:
        y = y + lora_fn(out_flat, lora["A_o"], lora["B_o"], lora_idx)
    return _channel_mix(cfg, p, x + y), ck, cv


def _decode_layer(cfg: ArchConfig, p, x, ck, cv, bt, pos, active,
                  attn_impl: str = "gather",
                  paged_fn=paged_ops.paged_decode,
                  lora=None, lora_idx=None, lora_fn=None):
    """One layer of a one-token step for ALL slots.

    x: (S, 1, d); ck/cv: (N, Hk, bs, hd); bt: (S, max_bps) block tables;
    pos: (S,) per-slot cursors; active: (S,) bool — inactive slots neither
    write KV nor advance (their scatter block id is forced out of bounds
    and dropped).  ``lora``/``lora_idx`` (S,)/``lora_fn`` apply per-slot
    grouped low-rank deltas (multi-tenant LoRA; -1 = base model).
    """
    N, bs = ck.shape[0], ck.shape[2]
    S_, max_bps = bt.shape
    L_virt = max_bps * bs
    h = apply_norm(cfg.norm_kind, x, p["ln1"])
    deltas = (None if lora is None
              else _qkv_deltas(cfg, h, lora, lora_idx, lora_fn))
    q, k_new, v_new = A._project_qkv(cfg, p["attn"], h, pos[:, None],
                                     deltas)
    rows = jnp.arange(S_, dtype=jnp.int32)
    blk = jnp.where(active, bt[rows, pos // bs], N)
    ck = ck.at[blk, :, pos % bs].set(k_new[:, 0].astype(ck.dtype))
    cv = cv.at[blk, :, pos % bs].set(v_new[:, 0].astype(cv.dtype))
    if attn_impl == "paged":
        # block-by-block flash decode per slot table — no page buffer,
        # blocks past each slot's cursor are skipped inside the kernel
        out = paged_fn(q[:, 0], ck, cv, bt, pos)
        out = out.reshape(S_, 1, -1)
    else:
        page_k, page_v = _pages(ck, bt), _pages(cv, bt)
        k_pos = jnp.arange(L_virt, dtype=jnp.int32)
        # per-slot causal mask over its virtual sequence (keys strictly
        # before + the token just written at pos)
        mask = (k_pos[None, :] <= pos[:, None])[:, None, None, None, :]
        out = A._gqa_scores_softmax_out(q, page_k.astype(x.dtype),
                                        page_v.astype(x.dtype), mask,
                                        cfg.head_dim ** -0.5)
    out_flat = out.reshape(S_, 1, -1)
    y = jnp.einsum("bshd,hde->bse",
                   out_flat.reshape(S_, 1, cfg.n_heads, cfg.head_dim),
                   p["attn"]["wo"])
    if lora is not None:
        y = y + lora_fn(out_flat, lora["A_o"], lora["B_o"], lora_idx)
    return _channel_mix(cfg, p, x + y), ck, cv


def _verify_layer(cfg: ArchConfig, p, x, ck, cv, bt, pos, active, valid_q,
                  attn_impl: str = "gather",
                  paged_fn=paged_ops.paged_verify,
                  lora=None, lora_idx=None, lora_fn=None):
    """One layer of a speculative-verify step: Q = k+1 queries per slot.

    x: (S, Q, d) — slot ``s``'s queries are its pending token plus its k
    draft proposals, at absolute positions ``pos[s] .. pos[s]+Q-1``;
    ck/cv: (N, Hk, bs, hd); bt: (S, max_bps); pos: (S,) cursors;
    valid_q: (S,) live queries per slot (budget-capped — padding queries
    neither write KV nor matter downstream; their scatter block id is
    forced out of bounds and dropped, mirroring prefill chunk padding).

    Candidate K/V are scattered into the slot's exclusively-owned
    writable blocks before attention, so query ``i`` causally attends the
    candidates ``<= i`` like a prefill chunk attends its own tokens.
    Rejected candidates stay in place past the rolled-back cursor:
    unreachable under the causal mask, overwritten by the next step.
    """
    N, bs = ck.shape[0], ck.shape[2]
    S_, max_bps = bt.shape
    Q = x.shape[1]
    L_virt = max_bps * bs
    h = apply_norm(cfg.norm_kind, x, p["ln1"])
    pos_q = pos[:, None] + jnp.arange(Q, dtype=jnp.int32)[None, :]  # (S, Q)
    deltas = (None if lora is None
              else _qkv_deltas(cfg, h, lora, lora_idx, lora_fn))
    q, k_new, v_new = A._project_qkv(cfg, p["attn"], h, pos_q, deltas)
    qi = jnp.arange(Q, dtype=jnp.int32)[None, :]
    live = active[:, None] & (qi < valid_q[:, None])
    rows = jnp.arange(S_, dtype=jnp.int32)[:, None]
    blk = jnp.where(live, bt[rows, pos_q // bs], N)
    ck = ck.at[blk, :, pos_q % bs].set(k_new.astype(ck.dtype))
    cv = cv.at[blk, :, pos_q % bs].set(v_new.astype(cv.dtype))
    if attn_impl == "paged":
        # one batched multi-query flash pass through every slot's table
        out = paged_fn(q, ck, cv, bt, pos)
        out = out.reshape(S_, Q, -1)
    else:
        page_k, page_v = _pages(ck, bt), _pages(cv, bt)
        k_pos = jnp.arange(L_virt, dtype=jnp.int32)
        # per-slot, per-query causal mask over the virtual sequence
        mask = (k_pos[None, None, :] <= pos_q[:, :, None])[:, None, None]
        out = A._gqa_scores_softmax_out(q, page_k.astype(x.dtype),
                                        page_v.astype(x.dtype), mask,
                                        cfg.head_dim ** -0.5)
    out_flat = out.reshape(S_, Q, -1)
    y = jnp.einsum("bshd,hde->bse",
                   out_flat.reshape(S_, Q, cfg.n_heads, cfg.head_dim),
                   p["attn"]["wo"])
    if lora is not None:
        y = y + lora_fn(out_flat, lora["A_o"], lora["B_o"], lora_idx)
    return _channel_mix(cfg, p, x + y), ck, cv


# ---------------------------------------------------------------------------
# jitted engine entry points
# ---------------------------------------------------------------------------

def make_engine_fns(cfg: ArchConfig, mesh: Mesh, policy: S.ShardingPolicy,
                    cache: BlockPagedKVCache, *, chunk_size: int,
                    decode_block: int, temperature: float = 0.0,
                    eos_id: Optional[int] = None,
                    attn_impl: str = "gather"):
    """Returns jit'd ``(prefill_fn, decode_fn, shardings)``.

    prefill_fn(params, state, tokens(1,C), slot, start, valid)
        -> (logits (V,), state)
    decode_fn(params, state, active(S,), remaining(S,), rng)
        -> (tokens (n,S), produced (n,S), active(S,), state)
    """
    from repro.models import act_sharding
    tp, pp = _check_impl_and_plan(cfg, mesh, policy, attn_impl)
    act_sharding.set_mesh(mesh, policy.dp_axes, policy.tp_axis)
    state_sh = cache.shardings(mesh, policy)
    param_sh = S.param_shardings(cfg, mesh, policy)

    paged_prefill_fn = paged_ops.paged_prefill
    paged_decode_fn = paged_ops.paged_decode
    if tp > 1 and attn_impl == "paged":
        # Pallas calls are opaque to GSPMD: shard them explicitly over the
        # KV-head axis — each chip runs the kernel against its own shard
        # of every cache block (no cross-chip traffic inside attention)
        tpa = policy.tp_axis
        head = P(None, tpa, None, None)      # (S|C, Hk, G, d)
        pool = P(None, tpa, None, None)      # (N, Hk, bs, d)
        paged_decode_fn = jax.shard_map(
            paged_ops.paged_decode, mesh=mesh,
            in_specs=(head, pool, pool, P(None, None), P(None)),
            out_specs=head, check_vma=False)
        paged_prefill_fn = jax.shard_map(
            paged_ops.paged_prefill, mesh=mesh,
            in_specs=(head, pool, pool, P(None), P(), P()),
            out_specs=head, check_vma=False)

    use_lora = cache.lora_slots > 0
    lora_fn = _make_lora_fn(cache, mesh, policy, attn_impl, tp)
    hoist_lora = use_lora and attn_impl == "gather"
    if hoist_lora:
        lora_fn = lora_ops.grouped_lora_pregathered

    def prefill(params, state, tokens, slot, start, valid):
        x = params["embed"][tokens]                       # (1, C, d)
        pos_q = start + jnp.arange(chunk_size, dtype=jnp.int32)
        valid_end = start + valid
        bt_slot = state["block_tables"][slot]             # (max_bps,)
        lora_idx = (state["adapter_slots"][slot][None] if use_lora
                    else None)

        def scan_fn(h, inp):
            p_layer, ck, cv = inp[:3]
            lora = inp[3] if use_lora else None
            h, ck, cv = _prefill_layer(cfg, p_layer, h, ck, cv, bt_slot,
                                       pos_q, valid_end, attn_impl,
                                       paged_prefill_fn,
                                       lora, lora_idx, lora_fn)
            return h, (ck, cv)

        xs = (params["layers"], state["cache_k"], state["cache_v"])
        if use_lora:
            lx = _lora_state_xs(state)
            xs = xs + (_pregather_lora(lx, lora_idx) if hoist_lora else lx,)
        x, (cks, cvs) = _staged_scan(scan_fn, x, xs, pp)
        x = apply_norm(cfg.norm_kind, x, params["ln_f"])
        h_last = jax.lax.dynamic_slice_in_dim(x, valid - 1, 1, axis=1)
        logits = _lm_head(cfg, params, h_last)[0, 0]      # (V,)
        new_state = dict(state)
        new_state["cache_k"], new_state["cache_v"] = cks, cvs
        new_state["pos"] = state["pos"].at[slot].add(valid)
        return logits, new_state

    def decode(params, state, active, remaining, rng):
        bt = state["block_tables"]
        lora_idx = state["adapter_slots"] if use_lora else None
        lora_xs = _lora_state_xs(state) if use_lora else None
        if hoist_lora:
            lora_xs = _pregather_lora(lora_xs, lora_idx)

        def step_fn(carry, _):
            ck_all, cv_all, pos, tok, act, rem, key = carry
            x = params["embed"][tok[:, None]]             # (S, 1, d)

            def layer_fn(h, inp):
                p_layer, ck, cv = inp[:3]
                lora = inp[3] if use_lora else None
                h, ck, cv = _decode_layer(cfg, p_layer, h, ck, cv, bt,
                                          pos, act, attn_impl,
                                          paged_decode_fn,
                                          lora, lora_idx, lora_fn)
                return h, (ck, cv)

            xs = (params["layers"], ck_all, cv_all)
            if use_lora:
                xs = xs + (lora_xs,)
            x, (cks, cvs) = _staged_scan(layer_fn, x, xs, pp)
            x = apply_norm(cfg.norm_kind, x, params["ln_f"])
            logits = _lm_head(cfg, params, x[:, -1:])[:, 0]   # (S, V)
            key, sub = jax.random.split(key)
            nxt = sample(logits, temperature, sub)
            produced = act
            hit_eos = ((nxt == eos_id) if eos_id is not None
                       else jnp.zeros_like(act))
            rem = rem - act.astype(jnp.int32)
            new_act = act & (rem > 0) & ~hit_eos
            pos = pos + act.astype(jnp.int32)
            tok = jnp.where(act, nxt, tok)
            out_tok = jnp.where(act, nxt, -1)
            return (cks, cvs, pos, tok, new_act, rem, key), (out_tok, produced)

        carry = (state["cache_k"], state["cache_v"], state["pos"],
                 state["tok"], active, remaining, rng)
        carry, (toks, produced) = jax.lax.scan(step_fn, carry, None,
                                               length=decode_block)
        cks, cvs, pos, tok, act, _, _ = carry
        new_state = dict(state)
        new_state["cache_k"], new_state["cache_v"] = cks, cvs
        new_state["pos"], new_state["tok"] = pos, tok
        return toks, produced, act, new_state

    prefill_fn = jax.jit(
        prefill,
        in_shardings=(param_sh, state_sh, None, None, None, None),
        out_shardings=(None, state_sh),
        donate_argnums=(1,))
    decode_fn = jax.jit(
        decode,
        in_shardings=(param_sh, state_sh, None, None, None),
        out_shardings=(None, None, None, state_sh),
        donate_argnums=(1,))
    return prefill_fn, decode_fn, {"params": param_sh, "state": state_sh}


def make_prefill_batch_fn(cfg: ArchConfig, mesh: Mesh,
                          policy: S.ShardingPolicy,
                          cache: BlockPagedKVCache, *,
                          attn_impl: str = "gather"):
    """Jit'd bucketed batched prefill-and-insert (traffic admission).

    prefill_batch_fn(params, state, qtoks (B, C), slots (B,),
                     valids (B,)) -> (logits (B, V), state)

    Admits up to B same-bucket prompt chunks in ONE dispatch set: member
    ``i``'s chunk lands in slot ``slots[i]`` at absolute positions
    ``pos[slots[i]] .. pos[slots[i]] + valids[i] - 1`` (``pos`` is each
    slot's KV cursor, so chunked admissions call this once per chunk
    index and the cursor advances by ``valids[i]`` each call).  Weight
    reads and dispatch launches amortize across the group — the
    admission-side analogue of batched decode, and the reason prefill
    -length bucketing pays (MaxText's MLPerf ``_prefill_insert_batch``).

    A member with ``valids[i] == 0`` is padding (groups are padded to a
    static B so one compiled shape serves every group size): its KV
    writes are dropped, its cursor does not advance, and its logits row
    is garbage the scheduler ignores.  The computation is exactly a
    speculative verify pass — per-slot multi-query attention through the
    block tables with live-masked scatter — so the layer body is
    ``_verify_layer`` over the group's gathered tables/cursors, and each
    member's first-token logits are read at its last valid position.
    """
    from repro.models import act_sharding
    tp, pp = _check_impl_and_plan(cfg, mesh, policy, attn_impl)
    act_sharding.set_mesh(mesh, policy.dp_axes, policy.tp_axis)
    state_sh = cache.shardings(mesh, policy)
    param_sh = S.param_shardings(cfg, mesh, policy)

    paged_verify_fn = paged_ops.paged_verify
    if tp > 1 and attn_impl == "paged":
        tpa = policy.tp_axis
        head = P(None, None, tpa, None, None)   # (B, C, Hk, G, d)
        pool = P(None, tpa, None, None)         # (N, Hk, bs, d)
        paged_verify_fn = jax.shard_map(
            paged_ops.paged_verify, mesh=mesh,
            in_specs=(head, pool, pool, P(None, None), P(None)),
            out_specs=head, check_vma=False)

    use_lora = cache.lora_slots > 0
    lora_fn = _make_lora_fn(cache, mesh, policy, attn_impl, tp)
    hoist_lora = use_lora and attn_impl == "gather"
    if hoist_lora:
        lora_fn = lora_ops.grouped_lora_pregathered

    def prefill_batch(params, state, qtoks, slots, valids):
        x = params["embed"][qtoks]                        # (B, C, d)
        bt = state["block_tables"][slots]                 # (B, max_bps)
        pos = state["pos"][slots]                         # (B,)
        active = valids > 0
        lora_idx = state["adapter_slots"][slots] if use_lora else None

        def layer_fn(h, inp):
            p_layer, ck, cv = inp[:3]
            lora = inp[3] if use_lora else None
            h, ck, cv = _verify_layer(cfg, p_layer, h, ck, cv, bt, pos,
                                      active, valids, attn_impl,
                                      paged_verify_fn,
                                      lora, lora_idx, lora_fn)
            return h, (ck, cv)

        xs = (params["layers"], state["cache_k"], state["cache_v"])
        if use_lora:
            lx = _lora_state_xs(state)
            xs = xs + (_pregather_lora(lx, lora_idx) if hoist_lora else lx,)
        x, (cks, cvs) = _staged_scan(layer_fn, x, xs, pp)
        x = apply_norm(cfg.norm_kind, x, params["ln_f"])
        # each member's first-token logits sit at its last valid position
        idx = jnp.clip(valids - 1, 0, x.shape[1] - 1)
        h_last = jnp.take_along_axis(x, idx[:, None, None], axis=1)
        logits = _lm_head(cfg, params, h_last)[:, 0]      # (B, V)
        new_state = dict(state)
        new_state["cache_k"], new_state["cache_v"] = cks, cvs
        # scatter-add tolerates duplicate padding slot ids (they add 0)
        new_state["pos"] = state["pos"].at[slots].add(
            jnp.where(active, valids, 0))
        return logits, new_state

    return jax.jit(
        prefill_batch,
        in_shardings=(param_sh, state_sh, None, None, None),
        out_shardings=(None, state_sh),
        donate_argnums=(1,))


def make_verify_fn(cfg: ArchConfig, mesh: Mesh, policy: S.ShardingPolicy,
                   cache: BlockPagedKVCache, *,
                   attn_impl: str = "gather"):
    """Jit'd speculative-verify entry point (retraced per qtoks width).

    verify_fn(params, state, qtoks (S, k+1), active (S,), valid_q (S,))
        -> (logits (S, k+1, V), state)

    ``qtoks[s]`` is slot ``s``'s pending token followed by its k draft
    proposals; their K/V land at absolute positions ``pos[s]..pos[s]+k``
    and every query's next-token logits come back so the scheduler can
    accept a prefix via rejection sampling.  The KV cursor is NOT
    advanced here — acceptance decides the advance, and the rejected
    tail needs no cleanup (causally unreachable, overwritten later).
    Padding queries (``qi >= valid_q[s]``, budget-capped) drop their KV
    writes like prefill chunk padding.
    """
    from repro.models import act_sharding
    tp, pp = _check_impl_and_plan(cfg, mesh, policy, attn_impl)
    act_sharding.set_mesh(mesh, policy.dp_axes, policy.tp_axis)
    state_sh = cache.shardings(mesh, policy)
    param_sh = S.param_shardings(cfg, mesh, policy)

    paged_verify_fn = paged_ops.paged_verify
    if tp > 1 and attn_impl == "paged":
        tpa = policy.tp_axis
        head = P(None, None, tpa, None, None)   # (S, Q, Hk, G, d)
        pool = P(None, tpa, None, None)         # (N, Hk, bs, d)
        paged_verify_fn = jax.shard_map(
            paged_ops.paged_verify, mesh=mesh,
            in_specs=(head, pool, pool, P(None, None), P(None)),
            out_specs=head, check_vma=False)

    use_lora = cache.lora_slots > 0
    lora_fn = _make_lora_fn(cache, mesh, policy, attn_impl, tp)
    hoist_lora = use_lora and attn_impl == "gather"
    if hoist_lora:
        lora_fn = lora_ops.grouped_lora_pregathered

    def verify(params, state, qtoks, active, valid_q):
        x = params["embed"][qtoks]                        # (S, Q, d)
        bt = state["block_tables"]
        pos = state["pos"]
        lora_idx = state["adapter_slots"] if use_lora else None

        def layer_fn(h, inp):
            p_layer, ck, cv = inp[:3]
            lora = inp[3] if use_lora else None
            h, ck, cv = _verify_layer(cfg, p_layer, h, ck, cv, bt, pos,
                                      active, valid_q, attn_impl,
                                      paged_verify_fn,
                                      lora, lora_idx, lora_fn)
            return h, (ck, cv)

        xs = (params["layers"], state["cache_k"], state["cache_v"])
        if use_lora:
            lx = _lora_state_xs(state)
            xs = xs + (_pregather_lora(lx, lora_idx) if hoist_lora else lx,)
        x, (cks, cvs) = _staged_scan(layer_fn, x, xs, pp)
        x = apply_norm(cfg.norm_kind, x, params["ln_f"])
        logits = _lm_head(cfg, params, x)                 # (S, Q, V)
        new_state = dict(state)
        new_state["cache_k"], new_state["cache_v"] = cks, cvs
        return logits, new_state

    return jax.jit(
        verify,
        in_shardings=(param_sh, state_sh, None, None, None),
        out_shardings=(None, state_sh),
        donate_argnums=(1,))
