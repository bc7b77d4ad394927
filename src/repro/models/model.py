"""Unified LM assembly for all assigned families.

* Homogeneous decoder stacks (dense / vlm / moe / ssm) are **stacked** and
  iterated with ``jax.lax.scan`` (MaxText-style): HLO size and compile time
  are O(1) in depth — llama3-405b's 126 layers lower as a single while loop.
* Heterogeneous stacks (hybrid RG-LRU patterns, whisper enc-dec) are
  unrolled (≤26 layers).
* Every family exposes: ``init_params`` / ``abstract_params`` /
  ``logical_axes`` / ``forward`` (+aux) / ``init_decode_state`` /
  ``prefill`` / ``decode_step``.
"""
from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from . import attention as A
from . import blocks as B
from .layers import (ParamDef, materialize, abstract, logical_axes as _laxes,
                     apply_norm, norm_defs, dense)
from .act_sharding import constrain


# ---------------------------------------------------------------------------
# parameter declaration
# ---------------------------------------------------------------------------

def _stack_defs(defs: Dict, n: int) -> Dict:
    """Prepend a stacked 'layers' axis to every ParamDef in a tree."""
    return jax.tree_util.tree_map(
        lambda d: ParamDef((n,) + d.shape, ("layers",) + d.axes,
                           init=d.init, dtype=d.dtype, scale=d.scale),
        defs, is_leaf=lambda x: isinstance(x, ParamDef))


def _layer_defs(cfg: ArchConfig, kind: str) -> Dict:
    """Defs for one decoder layer of the given temporal-mixer kind."""
    d = {"ln1": norm_defs(cfg.norm_kind, cfg.d_model)}
    if kind == "attn":
        d["attn"] = A.attention_defs(cfg)
        if cfg.n_encoder_layers:
            d["ln_x"] = norm_defs(cfg.norm_kind, cfg.d_model)
            d["xattn"] = A.cross_attention_defs(cfg)
    elif kind == "ssm":
        d["ssm"] = B.mamba_defs(cfg)
    elif kind == "rglru":
        d["rglru"] = B.rglru_defs(cfg)
    if kind != "ssm" and (cfg.d_ff or cfg.family == "moe"):
        d["ln2"] = norm_defs(cfg.norm_kind, cfg.d_model)
        d["mlp"] = B.moe_defs(cfg) if cfg.family == "moe" else B.mlp_defs(cfg)
    return d


def param_defs(cfg: ArchConfig) -> Dict:
    defs: Dict = {
        "embed": ParamDef((cfg.vocab_size, cfg.d_model), ("vocab", "embed")),
        "ln_f": norm_defs(cfg.norm_kind, cfg.d_model),
    }
    if not cfg.tie_embeddings:
        defs["lm_head"] = ParamDef((cfg.d_model, cfg.vocab_size),
                                   ("embed", "vocab"))
    kinds = cfg.block_kinds()
    if cfg.family in ("dense", "vlm", "moe", "ssm"):
        defs["layers"] = _stack_defs(_layer_defs(cfg, kinds[0]), cfg.n_layers)
    else:  # hybrid / encdec: unrolled, possibly heterogeneous
        defs["layers"] = {f"l{i}": _layer_defs(cfg, k)
                          for i, k in enumerate(kinds)}
    if cfg.family == "vlm":
        defs["vision_proj"] = ParamDef((cfg.d_model, cfg.d_model),
                                       ("embed", "embed_out"))
    if cfg.family == "encdec":
        enc_layer = {
            "ln1": norm_defs(cfg.norm_kind, cfg.d_model),
            "attn": A.attention_defs(cfg),
            "ln2": norm_defs(cfg.norm_kind, cfg.d_model),
            "mlp": B.mlp_defs(cfg),
        }
        defs["encoder"] = {
            "layers": _stack_defs(enc_layer, cfg.n_encoder_layers),
            "ln_f": norm_defs(cfg.norm_kind, cfg.d_model),
        }
    return defs


def init_params(cfg: ArchConfig, rng: jax.Array, shardings=None) -> Dict:
    """Random parameters from ``rng``, generated by one jitted program.

    ``shardings`` (a tree like ``runtime.sharding.param_shardings``)
    places each leaf as it is made, so a model larger than one device
    never has to exist whole on the default device."""
    make = functools.partial(materialize, param_defs(cfg))
    return jax.jit(make, out_shardings=shardings)(rng)


def abstract_params(cfg: ArchConfig) -> Dict:
    return abstract(param_defs(cfg))


def logical_axes(cfg: ArchConfig) -> Dict:
    return _laxes(param_defs(cfg))


# ---------------------------------------------------------------------------
# layer bodies (sequence-level — train / prefill without cache)
# ---------------------------------------------------------------------------

def _seq_block(cfg: ArchConfig, kind: str, p: Dict, x: jax.Array,
               use_flash: bool, enc_kv=None) -> Tuple[jax.Array, jax.Array]:
    aux = jnp.zeros((), jnp.float32)
    x = constrain(x, ("batch", None, None))
    h = apply_norm(cfg.norm_kind, x, p["ln1"])
    if kind == "attn":
        if cfg.mla is not None:
            y = A.mla_self_attention(cfg, p["attn"], h)
        else:
            y = A.self_attention(cfg, p["attn"], h, causal=True,
                                 window=cfg.local_window or None,
                                 use_flash=use_flash)
        x = x + y
        if cfg.n_encoder_layers and enc_kv is not None:
            hx = apply_norm(cfg.norm_kind, x, p["ln_x"])
            x = x + A.cross_attention(cfg, p["xattn"], hx, *enc_kv)
    elif kind == "ssm":
        return x + B.mamba_forward(cfg, p["ssm"], h), aux
    elif kind == "rglru":
        x = x + B.rglru_forward(cfg, p["rglru"], h)
    if "mlp" in p:
        h = apply_norm(cfg.norm_kind, x, p["ln2"])
        if cfg.family == "moe":
            y, aux = B.moe_forward(cfg, p["mlp"], h)
        else:
            y = B.mlp_forward(cfg, p["mlp"], h)
        x = x + y
    return x, aux


#: remat policy names -> jax.checkpoint policies ("full" = save nothing)
REMAT_POLICIES = {
    "full": None,
    "dots": jax.checkpoint_policies.checkpoint_dots,
    "dots_no_batch": jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
}


def _run_stack(cfg: ArchConfig, params: Dict, x: jax.Array, *,
               use_flash: bool, remat: bool, enc_kv=None,
               remat_policy: str = "full"):
    """Iterate decoder layers; scan when stacked, unrolled otherwise."""
    kinds = cfg.block_kinds()
    aux_total = jnp.zeros((), jnp.float32)
    policy = REMAT_POLICIES.get(remat_policy)
    ckpt = (functools.partial(jax.checkpoint, policy=policy) if policy
            else jax.checkpoint)
    if cfg.family in ("dense", "vlm", "moe", "ssm"):
        body = functools.partial(_seq_block, cfg, kinds[0],
                                 use_flash=use_flash, enc_kv=enc_kv)

        def scan_fn(carry, p_layer):
            h, aux = carry
            h2, a = (ckpt(lambda pp, hh: body(pp, hh))(p_layer, h)
                     if remat else body(p_layer, h))
            return (h2, aux + a), None

        (x, aux_total), _ = jax.lax.scan(scan_fn, (x, aux_total),
                                         params["layers"])
    else:
        for i, kind in enumerate(kinds):
            p_layer = params["layers"][f"l{i}"]
            fn = functools.partial(_seq_block, cfg, kind, use_flash=use_flash,
                                   enc_kv=enc_kv if kind == "attn" else None)
            if remat:
                x, a = ckpt(lambda pp, hh, f=fn: f(pp, hh))(p_layer, x)
            else:
                x, a = fn(p_layer, x)
            aux_total = aux_total + a
    return x, aux_total


def _encoder_forward(cfg: ArchConfig, params: Dict, frames: jax.Array,
                     remat: bool = False) -> jax.Array:
    """Whisper-style encoder over precomputed (stub) frame embeddings."""
    x = frames

    def body(p, h):
        z = apply_norm(cfg.norm_kind, h, p["ln1"])
        h = h + A.self_attention(cfg, p["attn"], z, causal=False)
        z = apply_norm(cfg.norm_kind, h, p["ln2"])
        return h + B.mlp_forward(cfg, p["mlp"], z), None

    def scan_fn(h, p_layer):
        return (jax.checkpoint(body)(p_layer, h)[0] if remat
                else body(p_layer, h)[0]), None

    x, _ = jax.lax.scan(scan_fn, x, params["encoder"]["layers"])
    return apply_norm(cfg.norm_kind, x, params["encoder"]["ln_f"])


# ---------------------------------------------------------------------------
# full forward (train / uncached)
# ---------------------------------------------------------------------------

def forward(cfg: ArchConfig, params: Dict, token_ids: jax.Array, *,
            vision_embeds: Optional[jax.Array] = None,
            frames: Optional[jax.Array] = None,
            use_flash: bool = False, remat: bool = False,
            remat_policy: str = "full") -> Tuple[jax.Array, jax.Array]:
    """Returns (logits (b, s, V), moe_aux_loss scalar)."""
    x = params["embed"][token_ids]
    enc_kv = None
    if cfg.family == "vlm" and vision_embeds is not None:
        vp = dense(vision_embeds, params["vision_proj"])
        x = jnp.concatenate([vp.astype(x.dtype), x], axis=1)
    if cfg.family == "encdec":
        assert frames is not None, "encdec forward needs encoder frames"
        enc_out = _encoder_forward(cfg, params, frames, remat=remat)
        enc_kv = "per-layer"   # computed inside each decoder layer
    if enc_kv is not None:
        # compute per-layer cross K/V lazily inside blocks: pass encoder out
        x, aux = _run_stack_encdec(cfg, params, x, enc_out, remat=remat)
    else:
        x, aux = _run_stack(cfg, params, x, use_flash=use_flash, remat=remat,
                            remat_policy=remat_policy)
    x = apply_norm(cfg.norm_kind, x, params["ln_f"])
    logits = _lm_head(cfg, params, x)
    return logits, aux


def _run_stack_encdec(cfg, params, x, enc_out, remat):
    aux = jnp.zeros((), jnp.float32)
    for i, kind in enumerate(cfg.block_kinds()):
        p_layer = params["layers"][f"l{i}"]
        kv = A.encode_cross_kv(cfg, p_layer["xattn"], enc_out)
        fn = functools.partial(_seq_block, cfg, kind, use_flash=False,
                               enc_kv=kv)
        if remat:
            x, a = jax.checkpoint(lambda pp, hh, f=fn: f(pp, hh))(p_layer, x)
        else:
            x, a = fn(p_layer, x)
        aux = aux + a
    return x, aux


def _lm_head(cfg: ArchConfig, params: Dict, x: jax.Array) -> jax.Array:
    if cfg.tie_embeddings:
        return jnp.einsum("bsd,vd->bsv", x, params["embed"])
    return jnp.einsum("bsd,dv->bsv", x, params["lm_head"])


# ---------------------------------------------------------------------------
# decode state
# ---------------------------------------------------------------------------

def init_decode_state(cfg: ArchConfig, batch: int, max_len: int,
                      kv_dtype=jnp.bfloat16) -> Dict:
    kinds = cfg.block_kinds()
    state: Dict = {"pos": jnp.zeros((), jnp.int32)}
    n_attn = sum(1 for k in kinds if k == "attn")
    n_ssm = sum(1 for k in kinds if k == "ssm")
    n_rg = sum(1 for k in kinds if k == "rglru")
    if n_attn:
        kv_len = min(max_len, cfg.local_window) if cfg.local_window else max_len
        (ks, vs) = A.kv_cache_shape(cfg, batch, kv_len)
        state["cache_k"] = jnp.zeros((n_attn,) + ks, kv_dtype)
        state["cache_v"] = jnp.zeros((n_attn,) + vs, kv_dtype)
        if cfg.local_window:
            state["cache_pos"] = jnp.full((n_attn, batch, kv_len), -1, jnp.int32)
    if n_ssm:
        cs, ss = B.mamba_state_shapes(cfg, batch)
        state["conv_state"] = jnp.zeros((n_ssm,) + cs, jnp.bfloat16)
        state["ssm_state"] = jnp.zeros((n_ssm,) + ss, jnp.float32)
    if n_rg:
        cs, hs = B.rglru_state_shapes(cfg, batch)
        state["rg_conv"] = jnp.zeros((n_rg,) + cs, jnp.bfloat16)
        state["rg_h"] = jnp.zeros((n_rg,) + hs, jnp.float32)
    if cfg.family == "encdec":
        F = cfg.encoder_len
        state["cross_k"] = jnp.zeros(
            (cfg.n_layers, batch, F, cfg.n_heads, cfg.head_dim), kv_dtype)
        state["cross_v"] = jnp.zeros_like(state["cross_k"])
    return state


def abstract_decode_state(cfg: ArchConfig, batch: int, max_len: int,
                          kv_dtype=jnp.bfloat16) -> Dict:
    state = jax.eval_shape(
        lambda: init_decode_state(cfg, batch, max_len, kv_dtype))
    return state


# ---------------------------------------------------------------------------
# cached step (prefill with s tokens, or decode with s=1)
# ---------------------------------------------------------------------------

def _cached_block(cfg: ArchConfig, kind: str, p: Dict, x, pos, layer_state,
                  cross_kv=None):
    """Process one layer against its cache slice; returns (x, new_state)."""
    new_state = dict(layer_state)
    x = constrain(x, ("batch", None, None))
    h = apply_norm(cfg.norm_kind, x, p["ln1"])
    if kind == "attn":
        if cfg.mla is not None:
            y, ck, cv = A.mla_cached_attention(
                cfg, p["attn"], h, layer_state["cache_k"],
                layer_state["cache_v"], pos)
        elif cfg.local_window:
            y, ck, cv, cp = _local_cached_attention(
                cfg, p["attn"], h, layer_state["cache_k"],
                layer_state["cache_v"], layer_state["cache_pos"], pos)
            new_state["cache_pos"] = cp
        else:
            y, ck, cv = A.cached_attention(
                cfg, p["attn"], h, layer_state["cache_k"],
                layer_state["cache_v"], pos)
        new_state["cache_k"], new_state["cache_v"] = ck, cv
        x = x + y
        if cross_kv is not None:
            hx = apply_norm(cfg.norm_kind, x, p["ln_x"])
            x = x + A.cross_attention(cfg, p["xattn"], hx, *cross_kv)
    elif kind == "ssm":
        y, cs, ss = B.mamba_step(cfg, p["ssm"], h,
                                 layer_state["conv_state"],
                                 layer_state["ssm_state"])
        new_state["conv_state"], new_state["ssm_state"] = cs, ss
        return x + y, new_state
    elif kind == "rglru":
        y, cs, hst = B.rglru_step(cfg, p["rglru"], h,
                                  layer_state["rg_conv"],
                                  layer_state["rg_h"])
        new_state["rg_conv"], new_state["rg_h"] = cs, hst
        x = x + y
    if "mlp" in p:
        h = apply_norm(cfg.norm_kind, x, p["ln2"])
        if cfg.family == "moe":
            y, _ = B.moe_forward(cfg, p["mlp"], h)
        else:
            y = B.mlp_forward(cfg, p["mlp"], h)
        x = x + y
    return x, new_state


def _local_cached_attention(cfg, p, x, cache_k, cache_v, cache_pos, pos):
    """Ring-buffer local attention (window W buffer, global-position mask).

    Long prefill (s ≥ W): prior cache cannot influence outputs beyond the
    window, so outputs come from blockwise windowed self-attention over the
    chunk and only the last W tokens are written to the ring (unique slots).
    """
    b, s, _ = x.shape
    W = cache_k.shape[1]
    positions = pos + jnp.arange(s, dtype=jnp.int32)[None, :]
    q, k_new, v_new = A._project_qkv(cfg, p, x, positions)
    scale = cfg.head_dim ** -0.5
    if s >= W:
        out = A.blockwise_attention(q, k_new, v_new, scale, causal=True,
                                    window=W)
        tail = jnp.arange(s - W, s, dtype=jnp.int32)
        slots = (pos + tail) % W
        cache_k = cache_k.at[:, slots].set(k_new[:, -W:].astype(cache_k.dtype))
        cache_v = cache_v.at[:, slots].set(v_new[:, -W:].astype(cache_v.dtype))
        cache_pos = cache_pos.at[:, slots].set(
            jnp.broadcast_to(positions[:, -W:], (b, W)))
    else:
        slots = (pos + jnp.arange(s, dtype=jnp.int32)) % W
        cache_k = cache_k.at[:, slots].set(k_new.astype(cache_k.dtype))
        cache_v = cache_v.at[:, slots].set(v_new.astype(cache_v.dtype))
        cache_pos = cache_pos.at[:, slots].set(
            jnp.broadcast_to(positions, (b, s)))
        kp = cache_pos[:, None, None, None, :]              # (b,1,1,1,W)
        qp = positions[:, None, None, :, None]              # (b,1,1,s,1)
        mask = (kp >= 0) & (kp <= qp) & (kp > qp - W)
        out = A._gqa_scores_softmax_out(q, cache_k.astype(x.dtype),
                                        cache_v.astype(x.dtype), mask, scale)
    y = jnp.einsum("bshd,hde->bse",
                   out.reshape(b, s, cfg.n_heads, cfg.head_dim), p["wo"])
    return y, cache_k, cache_v, cache_pos


def _split_layer_state(cfg: ArchConfig, state: Dict):
    """Per-layer views of the stacked decode state (for unrolled stacks)."""
    kinds = cfg.block_kinds()
    ia = isa = irg = 0
    per_layer = []
    for kind in kinds:
        s: Dict = {}
        if kind == "attn":
            s["cache_k"] = state["cache_k"][ia]
            s["cache_v"] = state["cache_v"][ia]
            if cfg.local_window:
                s["cache_pos"] = state["cache_pos"][ia]
            s["_idx"] = ("attn", ia)
            ia += 1
        elif kind == "ssm":
            s["conv_state"] = state["conv_state"][isa]
            s["ssm_state"] = state["ssm_state"][isa]
            s["_idx"] = ("ssm", isa)
            isa += 1
        elif kind == "rglru":
            s["rg_conv"] = state["rg_conv"][irg]
            s["rg_h"] = state["rg_h"][irg]
            s["_idx"] = ("rglru", irg)
            irg += 1
        per_layer.append(s)
    return per_layer


_STATE_KEYS = {
    "attn": [("cache_k", "cache_k"), ("cache_v", "cache_v"),
             ("cache_pos", "cache_pos")],
    "ssm": [("conv_state", "conv_state"), ("ssm_state", "ssm_state")],
    "rglru": [("rg_conv", "rg_conv"), ("rg_h", "rg_h")],
}


def step(cfg: ArchConfig, params: Dict, token_ids: jax.Array, state: Dict, *,
         vision_embeds: Optional[jax.Array] = None,
         frames: Optional[jax.Array] = None) -> Tuple[jax.Array, Dict]:
    """Cached model step: prefill (s = prompt len) or decode (s = 1).

    Returns (logits for the final position (b, V), new state).
    """
    pos = state["pos"]
    x = params["embed"][token_ids]
    if cfg.family == "vlm" and vision_embeds is not None:
        vp = dense(vision_embeds, params["vision_proj"])
        x = jnp.concatenate([vp.astype(x.dtype), x], axis=1)
    new_state = dict(state)
    if cfg.family == "encdec" and frames is not None:
        enc_out = _encoder_forward(cfg, params, frames)
        # stack the per-layer cross-KV projections and encode every layer
        # in one vmapped computation (consistent with the scanned
        # homogeneous stacks: one HLO op regardless of depth) — the
        # decoder layers themselves stay dict-unrolled (heterogeneous)
        xkv = {name: jnp.stack([params["layers"][f"l{i}"]["xattn"][name]
                                for i in range(cfg.n_layers)])
               for name in ("wk", "wv")}
        cks, cvs = jax.vmap(
            lambda p: A.encode_cross_kv(cfg, p, enc_out))(xkv)
        new_state["cross_k"] = cks.astype(state["cross_k"].dtype)
        new_state["cross_v"] = cvs.astype(state["cross_v"].dtype)

    kinds = cfg.block_kinds()
    if cfg.family in ("dense", "vlm", "moe"):
        # scan over stacked layers, threading stacked caches as xs/ys
        def scan_fn(carry, inp):
            h = carry
            p_layer, ck, cv = inp
            ls = {"cache_k": ck, "cache_v": cv}
            h, ns = _cached_block(cfg, kinds[0], p_layer, h, pos, ls)
            return h, (ns["cache_k"], ns["cache_v"])

        x, (cks, cvs) = jax.lax.scan(
            scan_fn, x, (params["layers"], state["cache_k"],
                         state["cache_v"]))
        new_state["cache_k"], new_state["cache_v"] = cks, cvs
    elif cfg.family == "ssm":
        def scan_fn(carry, inp):
            h = carry
            p_layer, cs, ss = inp
            ls = {"conv_state": cs, "ssm_state": ss}
            h, ns = _cached_block(cfg, "ssm", p_layer, h, pos, ls)
            return h, (ns["conv_state"], ns["ssm_state"])

        x, (css, sss) = jax.lax.scan(
            scan_fn, x, (params["layers"], state["conv_state"],
                         state["ssm_state"]))
        new_state["conv_state"], new_state["ssm_state"] = css, sss
    else:
        per_layer = _split_layer_state(cfg, state)
        updated = {k: [None] * v.shape[0] for k, v in state.items()
                   if k not in ("pos", "cross_k", "cross_v")}
        for i, kind in enumerate(kinds):
            p_layer = params["layers"][f"l{i}"]
            ls = per_layer[i]
            kind_name, idx = ls.pop("_idx")
            cross = ((new_state["cross_k"][i], new_state["cross_v"][i])
                     if cfg.family == "encdec" else None)
            x, ns = _cached_block(cfg, kind, p_layer, x, pos, ls,
                                  cross_kv=cross)
            for skey, lkey in _STATE_KEYS[kind_name]:
                if lkey in ns:
                    updated[skey][idx] = ns[lkey]
        for k, vals in updated.items():
            got = [v for v in vals if v is not None]
            if got:
                new_state[k] = jnp.stack(got)
    x = apply_norm(cfg.norm_kind, x, params["ln_f"])
    logits = _lm_head(cfg, params, x[:, -1:, :])[:, 0]
    new_state["pos"] = pos + token_ids.shape[1] + (
        vision_embeds.shape[1] if (cfg.family == "vlm"
                                   and vision_embeds is not None) else 0)
    return logits, new_state
