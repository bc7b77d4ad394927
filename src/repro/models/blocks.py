"""Per-family block implementations: MLP, MoE (scatter dispatch + capacity),
Mamba-1 selective SSM, RG-LRU (Griffin) — each with parameter defs, a
sequence-level forward (train/prefill) and a single-token step (decode).
"""
from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from .layers import ParamDef, dense, silu, gelu
from .act_sharding import constrain


# ---------------------------------------------------------------------------
# dense MLP
# ---------------------------------------------------------------------------

def mlp_defs(cfg: ArchConfig) -> Dict:
    d, f = cfg.d_model, cfg.d_ff
    defs = {"up": ParamDef((d, f), ("embed", "mlp")),
            "down": ParamDef((f, d), ("mlp", "embed"))}
    if cfg.gated_mlp:
        defs["gate"] = ParamDef((d, f), ("embed", "mlp"))
    return defs


def mlp_forward(cfg: ArchConfig, p: Dict, x: jax.Array) -> jax.Array:
    if cfg.gated_mlp:
        return dense(silu(dense(x, p["gate"])) * dense(x, p["up"]), p["down"])
    return dense(gelu(dense(x, p["up"])), p["down"])


# ---------------------------------------------------------------------------
# MoE: top-k routing, capacity-bounded scatter dispatch, shared experts
# ---------------------------------------------------------------------------

def moe_defs(cfg: ArchConfig) -> Dict:
    d, f, E = cfg.d_model, cfg.d_ff_expert, cfg.n_experts
    fs = cfg.n_shared_experts * f
    defs = {
        "router": ParamDef((d, E), ("embed", "experts")),
        "w_gate": ParamDef((E, d, f), ("experts", "embed", "expert_mlp")),
        "w_up": ParamDef((E, d, f), ("experts", "embed", "expert_mlp")),
        "w_down": ParamDef((E, f, d), ("experts", "expert_mlp", "embed")),
    }
    if fs:
        defs["shared_gate"] = ParamDef((d, fs), ("embed", "mlp"))
        defs["shared_up"] = ParamDef((d, fs), ("embed", "mlp"))
        defs["shared_down"] = ParamDef((fs, d), ("mlp", "embed"))
    return defs


#: MoE dispatch mode: "local" keeps the batch dimension so the dispatch is
#: per-row (DP-shardable, capacity from LOCAL tokens, EP over padded expert
#: count); "global" is the naive flat-token dispatch — kept for the §Perf
#: baseline, where it measurably replicates expert compute across the mesh.
MOE_DISPATCH = "local"
#: experts are padded up to a multiple of this so the expert axis divides
#: the tensor-parallel mesh axis (EP); dead experts are never routed to.
MOE_EXPERT_PAD_TO = 16


def moe_forward(cfg: ArchConfig, p: Dict, x: jax.Array,
                capacity_factor: float = 1.25
                ) -> Tuple[jax.Array, jax.Array]:
    """Returns (output, load-balance aux loss).

    Dispatch is scatter-based (indices, not one-hot einsums) so compiled
    FLOPs reflect only useful expert compute — the dispatch/combine shows up
    as memory traffic and (under EP sharding) all-to-all collectives.

    Modes (``MOE_DISPATCH``): "local" (default — per-row capacity, XLA
    chooses the EP collectives), "a2a" (shard_map expert-parallel with an
    explicit token-granular psum combine — §Perf A4), "global" (naive
    baseline).
    """
    if MOE_DISPATCH == "a2a":
        return _moe_forward_a2a(cfg, p, x, capacity_factor)
    if MOE_DISPATCH == "local":
        return _moe_forward_local(cfg, p, x, capacity_factor)
    return _moe_forward_global(cfg, p, x, capacity_factor)


def _router(cfg: ArchConfig, p: Dict, xt: jax.Array):
    """Top-k routing + Switch-style load-balance aux on flat tokens."""
    E, k = cfg.n_experts, cfg.top_k
    logits = dense(xt, p["router"]).astype(jnp.float32)          # (..., E)
    probs = jax.nn.softmax(logits, axis=-1)
    weights, sel = jax.lax.top_k(probs, k)                       # (..., k)
    weights = (weights / (weights.sum(-1, keepdims=True) + 1e-9))
    assign = jax.nn.one_hot(sel[..., 0], E, dtype=jnp.float32)
    flat = (-1, E)
    aux = E * jnp.sum(assign.reshape(flat).mean(0)
                      * probs.reshape(flat).mean(0))
    return weights.astype(xt.dtype), sel, aux


def _pad_experts(p: Dict, E: int) -> Tuple[Dict, int]:
    """Pad stacked expert weights so E divides the EP mesh axis."""
    E_pad = -(-E // MOE_EXPERT_PAD_TO) * MOE_EXPERT_PAD_TO
    if E_pad == E:
        return p, E
    pads = ((0, E_pad - E), (0, 0), (0, 0))
    return {**p,
            "w_gate": jnp.pad(p["w_gate"], pads),
            "w_up": jnp.pad(p["w_up"], pads),
            "w_down": jnp.pad(p["w_down"], pads)}, E_pad


def _moe_forward_local(cfg, p, x, capacity_factor):
    """Per-row dispatch: capacity from LOCAL tokens, batch dim preserved.

    Buffers are (b, E_pad, C_row, d) with b → dp and E_pad → model (EP):
    the dispatch scatter is row-local so SPMD partitions it without
    replication; cross-row imbalance is absorbed by the per-row capacity
    factor (tokens over capacity drop, standard Switch semantics).
    """
    b, s, d = x.shape
    E, k = cfg.n_experts, cfg.top_k
    weights, sel, aux = _router(cfg, p, x)        # (b, s, k)
    pe, E_pad = _pad_experts(p, E)

    capacity = max(1, int(math.ceil(s * k * capacity_factor / E)))
    flat_e = sel.reshape(b, s * k)                               # (b, s·k)
    onehot = jax.nn.one_hot(flat_e, E_pad, dtype=jnp.int32)      # (b, s·k, E)
    pos = jnp.cumsum(onehot, axis=1) - onehot
    pos_in_e = jnp.take_along_axis(pos, flat_e[..., None], axis=2)[..., 0]

    x_rep = jnp.repeat(x, k, axis=1)                             # (b, s·k, d)
    rows = jnp.arange(b, dtype=jnp.int32)[:, None]
    buf = jnp.zeros((b, E_pad, capacity, d), x.dtype)
    buf = buf.at[rows, flat_e, pos_in_e].set(x_rep, mode="drop")
    # dispatch buffer stays batch-sharded / expert-REPLICATED: the scatter
    # is then rank-local (no resharding); EP happens at the einsums, whose
    # outputs shard on the expert axis because the weights do (§Perf A2 —
    # sharding buf on experts forced a 2.5x collective blow-up).
    buf = constrain(buf, ("batch", None, None, None))

    h = silu(jnp.einsum("becd,edf->becf", buf, pe["w_gate"])) \
        * jnp.einsum("becd,edf->becf", buf, pe["w_up"])
    h = constrain(h, ("batch", "experts", None, None))
    out_buf = jnp.einsum("becf,efd->becd", h, pe["w_down"])
    # re-replicate the (small) output buffer over the model axis BEFORE the
    # combine gather: one explicit all-gather of E·C·d per rank instead of
    # XLA's cross-shard-gather fallback, which replicated full-global-batch
    # f32 tensors and all-reduced them (§Perf A3: 8 TB → ~0.3 TB wire).
    out_buf = constrain(out_buf, ("batch", None, None, None))

    y_rep = out_buf.at[rows, flat_e, pos_in_e].get(mode="fill", fill_value=0)
    y = (y_rep.reshape(b, s, k, d)
         * weights[..., None]).sum(axis=2)

    if "shared_gate" in p:
        y = y + dense(silu(dense(x, p["shared_gate"]))
                      * dense(x, p["shared_up"]), p["shared_down"])
    return y, aux


def _moe_forward_a2a(cfg, p, x, capacity_factor):
    """shard_map expert parallelism with token-granular combine (§Perf A4).

    The dispatch buffer stays rank-local (batch-sharded, expert-replicated,
    like "local"); inside a shard_map over the model axis each rank computes
    ONLY its expert chunk (weights arrive pre-sharded, no gather) and
    contributes its tokens' outputs through a single bf16 psum — replacing
    XLA's f32 capacity-buffer gathers with the minimal token-sized exchange.
    Falls back to "local" when no mesh hint is installed (1-device tests) or
    the padded expert count doesn't divide the model axis.
    """
    from .act_sharding import _HINT
    mesh = _HINT["mesh"]
    tp = _HINT["tp"]
    E, k = cfg.n_experts, cfg.top_k
    E_pad = -(-E // MOE_EXPERT_PAD_TO) * MOE_EXPERT_PAD_TO
    if (mesh is None or tp is None or E_pad % mesh.shape[tp] != 0
            or mesh.shape[tp] == 1):
        return _moe_forward_local(cfg, p, x, capacity_factor)
    from jax.sharding import PartitionSpec as P

    b, s, d = x.shape
    weights, sel, aux = _router(cfg, p, x)        # (b, s, k)
    pe, _ = _pad_experts(p, E)

    capacity = max(1, int(math.ceil(s * k * capacity_factor / E)))
    flat_e = sel.reshape(b, s * k)
    onehot = jax.nn.one_hot(flat_e, E_pad, dtype=jnp.int32)
    pos_in_e = jnp.cumsum(onehot, axis=1) - onehot
    pos_in_e = jnp.take_along_axis(pos_in_e, flat_e[..., None], axis=2)[..., 0]
    rows = jnp.arange(b, dtype=jnp.int32)[:, None]
    x_rep = jnp.repeat(x, k, axis=1)
    buf = jnp.zeros((b, E_pad, capacity, d), x.dtype)
    buf = buf.at[rows, flat_e, pos_in_e].set(x_rep, mode="drop")
    buf = constrain(buf, ("batch", None, None, None))

    dp = _HINT["dp"]
    n_tp = mesh.shape[tp]
    e_loc = E_pad // n_tp
    rest = tuple(a for a in mesh.axis_names if a not in dp + (tp,))

    def expert_chunk(buf_l, wg_l, wu_l, wd_l, flat_e_l, pos_l, wts_l):
        j = jax.lax.axis_index(tp)
        # slice this rank's expert chunk out of the local dispatch buffer
        buf_j = jax.lax.dynamic_slice_in_dim(buf_l, j * e_loc, e_loc, axis=1)
        h = silu(jnp.einsum("becd,edf->becf", buf_j, wg_l)) \
            * jnp.einsum("becd,edf->becf", buf_j, wu_l)
        out_j = jnp.einsum("becf,efd->becd", h, wd_l)   # (b_l, e_loc, C, d)
        # token-granular combine: only entries routed to this chunk
        rel = flat_e_l - j * e_loc
        valid = (rel >= 0) & (rel < e_loc)
        rel_c = jnp.clip(rel, 0, e_loc - 1)
        rows_l = jnp.arange(buf_l.shape[0], dtype=jnp.int32)[:, None]
        y_rep = out_j[rows_l, rel_c, pos_l]              # (b_l, s·k, d)
        y_rep = jnp.where(valid[..., None], y_rep, 0)
        y = (y_rep.reshape(buf_l.shape[0], s, k, d)
             * wts_l[..., None].astype(y_rep.dtype)).sum(axis=2)
        return jax.lax.psum(y, tp)                       # bf16 token exchange

    y = jax.shard_map(
        expert_chunk, mesh=mesh,
        in_specs=(P(dp), P(tp), P(tp), P(tp), P(dp), P(dp), P(dp)),
        out_specs=P(dp),
        check_vma=False,
    )(buf, pe["w_gate"], pe["w_up"], pe["w_down"], flat_e, pos_in_e, weights)

    if "shared_gate" in p:
        y = y + dense(silu(dense(x, p["shared_gate"]))
                      * dense(x, p["shared_up"]), p["shared_down"])
    return y, aux


def _moe_forward_global(cfg, p, x, capacity_factor):
    """Naive flat-token dispatch (the §Perf baseline): capacity from GLOBAL
    tokens; the cross-shard scatter forces SPMD to replicate expert
    compute when E doesn't divide the mesh — measured in EXPERIMENTS.md."""
    b, s, d = x.shape
    T = b * s
    E, k = cfg.n_experts, cfg.top_k
    xt = x.reshape(T, d)
    weights, sel, aux = _router(cfg, p, xt)

    capacity = max(1, int(math.ceil(T * k * capacity_factor / E)))
    flat_e = sel.reshape(-1)                                     # (T·k,)
    onehot = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)
    pos_in_e = (jnp.cumsum(onehot, axis=0) - onehot)             # (T·k, E)
    pos_in_e = jnp.take_along_axis(pos_in_e, flat_e[:, None], axis=1)[:, 0]

    x_rep = jnp.repeat(xt, k, axis=0)                            # (T·k, d)
    buf = jnp.zeros((E, capacity, d), x.dtype)
    buf = buf.at[flat_e, pos_in_e].set(x_rep, mode="drop")       # overflow drops
    buf = constrain(buf, ("experts", None, None))                # EP dispatch

    h = silu(jnp.einsum("ecd,edf->ecf", buf, p["w_gate"])) \
        * jnp.einsum("ecd,edf->ecf", buf, p["w_up"])
    h = constrain(h, ("experts", None, None))
    out_buf = jnp.einsum("ecf,efd->ecd", h, p["w_down"])
    out_buf = constrain(out_buf, ("experts", None, None))

    y_rep = out_buf.at[flat_e, pos_in_e].get(mode="fill", fill_value=0)
    y = (y_rep.reshape(T, k, d) * weights[..., None]).sum(axis=1)

    if "shared_gate" in p:
        y = y + dense(silu(dense(xt, p["shared_gate"]))
                      * dense(xt, p["shared_up"]), p["shared_down"])
    return y.reshape(b, s, d), aux


# ---------------------------------------------------------------------------
# Mamba-1 selective SSM
# ---------------------------------------------------------------------------

def mamba_defs(cfg: ArchConfig) -> Dict:
    d = cfg.d_model
    di = cfg.ssm_expand * d
    n = cfg.ssm_d_state
    dtr = cfg.ssm_dt_rank or max(1, d // 16)
    K = cfg.ssm_conv_kernel
    return {
        "in_proj": ParamDef((d, 2 * di), ("embed", "inner")),
        "conv_w": ParamDef((di, K), ("inner", None)),
        "conv_b": ParamDef((di,), ("inner",), init="zeros"),
        "x_proj": ParamDef((di, dtr + 2 * n), ("inner", None)),
        "dt_proj": ParamDef((dtr, di), (None, "inner")),
        "dt_bias": ParamDef((di,), ("inner",), init="zeros"),
        "A_log": ParamDef((di, n), ("inner", "state"), init="mamba_a",
                          dtype=jnp.float32),
        "D": ParamDef((di,), ("inner",), init="ones", dtype=jnp.float32),
        "out_proj": ParamDef((di, d), ("inner", "embed")),
    }


def _causal_depthwise_conv(x: jax.Array, w: jax.Array, b: jax.Array,
                           state: Optional[jax.Array] = None):
    """x: (b, s, C); w: (C, K). Returns (y, new_state (b, K-1, C))."""
    K = w.shape[1]
    if state is None:
        state = jnp.zeros((x.shape[0], K - 1, x.shape[2]), x.dtype)
    xp = jnp.concatenate([state, x], axis=1)            # (b, K-1+s, C)
    y = sum(xp[:, i:i + x.shape[1], :] * w[:, i] for i in range(K)) + b
    new_state = xp[:, -(K - 1):, :] if K > 1 else state
    return y, new_state


def _ssm_params(cfg: ArchConfig, p: Dict, x_c: jax.Array):
    dtr = cfg.ssm_dt_rank or max(1, cfg.d_model // 16)
    n = cfg.ssm_d_state
    xp = dense(x_c, p["x_proj"])
    dt_raw, B, C = jnp.split(xp, [dtr, dtr + n], axis=-1)
    dt = jax.nn.softplus(dense(dt_raw, p["dt_proj"]).astype(jnp.float32)
                         + p["dt_bias"].astype(jnp.float32))
    A = -jnp.exp(p["A_log"])                             # (di, n)
    return dt, A, B.astype(jnp.float32), C.astype(jnp.float32)


#: sequences above this are processed in streamed chunks (activation memory
#: for d_inner×seq would not fit otherwise at 32k+ contexts)
SSM_CHUNK = 1024


def _mamba_seq(cfg: ArchConfig, p: Dict, x: jax.Array, conv_state, ssm_state):
    """One contiguous chunk; threads (conv_state, ssm_state) through."""
    b, s, d = x.shape
    xz = dense(x, p["in_proj"])
    x_in, z = jnp.split(xz, 2, axis=-1)
    x_in = constrain(x_in, ("batch", None, "inner"))
    x_c, conv_state = _causal_depthwise_conv(x_in, p["conv_w"], p["conv_b"],
                                             conv_state)
    x_c = silu(x_c)
    dt, A, B, C = _ssm_params(cfg, p, x_c)
    dt = constrain(dt, ("batch", None, "inner"))
    xf = x_c.astype(jnp.float32)

    def step(h, inp):
        dt_t, B_t, C_t, x_t = inp                        # (b,di),(b,n),(b,n),(b,di)
        dA = jnp.exp(dt_t[..., None] * A)                # (b,di,n)
        dBx = dt_t[..., None] * B_t[:, None, :] * x_t[..., None]
        h = h * dA + dBx
        y = jnp.einsum("bdn,bn->bd", h, C_t)
        return h, y

    xs = (jnp.moveaxis(dt, 1, 0), jnp.moveaxis(B, 1, 0),
          jnp.moveaxis(C, 1, 0), jnp.moveaxis(xf, 1, 0))
    ssm_state, ys = jax.lax.scan(step, ssm_state, xs)
    y = jnp.moveaxis(ys, 0, 1) + xf * p["D"]
    out = y.astype(x.dtype) * silu(z)
    return dense(out, p["out_proj"]), conv_state, ssm_state


def mamba_forward(cfg: ArchConfig, p: Dict, x: jax.Array) -> jax.Array:
    """Sequence forward; long sequences stream in SSM_CHUNK pieces."""
    b, s, d = x.shape
    di = cfg.ssm_expand * d
    conv0 = jnp.zeros((b, cfg.ssm_conv_kernel - 1, di), x.dtype)
    ssm0 = jnp.zeros((b, di, cfg.ssm_d_state), jnp.float32)
    if s <= SSM_CHUNK or s % SSM_CHUNK != 0:
        y, _, _ = _mamba_seq(cfg, p, x, conv0, ssm0)
        return y
    n = s // SSM_CHUNK
    xc = jnp.moveaxis(x.reshape(b, n, SSM_CHUNK, d), 1, 0)

    def chunk(carry, x_i):
        conv_s, ssm_s = carry
        y_i, conv_s, ssm_s = _mamba_seq(cfg, p, x_i, conv_s, ssm_s)
        return (conv_s, ssm_s), y_i

    _, ys = jax.lax.scan(chunk, (conv0, ssm0), xc)
    return jnp.moveaxis(ys, 0, 1).reshape(b, s, d)


def mamba_step(cfg: ArchConfig, p: Dict, x_t: jax.Array,
               conv_state: jax.Array, ssm_state: jax.Array):
    """Single decode token. x_t: (b, 1, d); states threaded explicitly."""
    xz = dense(x_t, p["in_proj"])
    x_in, z = jnp.split(xz, 2, axis=-1)
    x_c, conv_state = _causal_depthwise_conv(x_in, p["conv_w"], p["conv_b"],
                                             conv_state)
    x_c = silu(x_c)
    dt, A, B, C = _ssm_params(cfg, p, x_c)
    dt_t, B_t, C_t = dt[:, 0], B[:, 0], C[:, 0]
    xf = x_c.astype(jnp.float32)[:, 0]
    dA = jnp.exp(dt_t[..., None] * A)
    ssm_state = ssm_state * dA + dt_t[..., None] * B_t[:, None, :] * xf[..., None]
    y = jnp.einsum("bdn,bn->bd", ssm_state, C_t) + xf * p["D"]
    out = y[:, None, :].astype(x_t.dtype) * silu(z)
    return dense(out, p["out_proj"]), conv_state, ssm_state


def mamba_state_shapes(cfg: ArchConfig, batch: int):
    di = cfg.ssm_expand * cfg.d_model
    return ((batch, cfg.ssm_conv_kernel - 1, di),      # conv state (bf16)
            (batch, di, cfg.ssm_d_state))              # ssm state (fp32)


# ---------------------------------------------------------------------------
# RG-LRU (Griffin / RecurrentGemma recurrent block)
# ---------------------------------------------------------------------------

_RGLRU_C = 8.0


def rglru_defs(cfg: ArchConfig) -> Dict:
    d = cfg.d_model
    w = cfg.lru_width or d
    K = cfg.ssm_conv_kernel
    return {
        "linear_x": ParamDef((d, w), ("embed", "inner")),
        "linear_y": ParamDef((d, w), ("embed", "inner")),
        "conv_w": ParamDef((w, K), ("inner", None)),
        "conv_b": ParamDef((w,), ("inner",), init="zeros"),
        "gate_i_w": ParamDef((w,), ("inner",), init="ones"),
        "gate_i_b": ParamDef((w,), ("inner",), init="zeros"),
        "gate_r_w": ParamDef((w,), ("inner",), init="ones"),
        "gate_r_b": ParamDef((w,), ("inner",), init="zeros"),
        "a_param": ParamDef((w,), ("inner",), init="ones", dtype=jnp.float32),
        "linear_out": ParamDef((w, d), ("inner", "embed")),
    }


def _rglru_gates(p, x_c):
    i = jax.nn.sigmoid(x_c * p["gate_i_w"] + p["gate_i_b"])
    r = jax.nn.sigmoid(x_c * p["gate_r_w"] + p["gate_r_b"])
    log_a = -_RGLRU_C * jax.nn.softplus(p["a_param"]) * r.astype(jnp.float32)
    a = jnp.exp(log_a)
    mult = jnp.sqrt(jnp.maximum(1.0 - jnp.square(a), 1e-6))
    return (i.astype(jnp.float32), a, mult)


def _rglru_seq(cfg: ArchConfig, p: Dict, x: jax.Array, conv_state, h_state):
    b, s, d = x.shape
    xb = constrain(dense(x, p["linear_x"]), ("batch", None, "inner"))
    yb = gelu(dense(x, p["linear_y"]))
    x_c, conv_state = _causal_depthwise_conv(xb, p["conv_w"], p["conv_b"],
                                             conv_state)
    i, a, mult = _rglru_gates(p, x_c)
    gated = (i * x_c.astype(jnp.float32)) * mult

    def step(h, inp):
        a_t, g_t = inp
        h = a_t * h + g_t
        return h, h

    h_state, hs = jax.lax.scan(step, h_state, (jnp.moveaxis(a, 1, 0),
                                               jnp.moveaxis(gated, 1, 0)))
    h = jnp.moveaxis(hs, 0, 1).astype(x.dtype)
    return dense(h * yb, p["linear_out"]), conv_state, h_state


def rglru_forward(cfg: ArchConfig, p: Dict, x: jax.Array) -> jax.Array:
    b, s, d = x.shape
    w = cfg.lru_width or d
    conv0 = jnp.zeros((b, cfg.ssm_conv_kernel - 1, w), x.dtype)
    h0 = jnp.zeros((b, w), jnp.float32)
    if s <= SSM_CHUNK or s % SSM_CHUNK != 0:
        y, _, _ = _rglru_seq(cfg, p, x, conv0, h0)
        return y
    n = s // SSM_CHUNK
    xc = jnp.moveaxis(x.reshape(b, n, SSM_CHUNK, d), 1, 0)

    def chunk(carry, x_i):
        conv_s, h_s = carry
        y_i, conv_s, h_s = _rglru_seq(cfg, p, x_i, conv_s, h_s)
        return (conv_s, h_s), y_i

    _, ys = jax.lax.scan(chunk, (conv0, h0), xc)
    return jnp.moveaxis(ys, 0, 1).reshape(b, s, d)


def rglru_step(cfg: ArchConfig, p: Dict, x_t: jax.Array,
               conv_state: jax.Array, h_state: jax.Array):
    xb = dense(x_t, p["linear_x"])
    yb = gelu(dense(x_t, p["linear_y"]))
    x_c, conv_state = _causal_depthwise_conv(xb, p["conv_w"], p["conv_b"],
                                             conv_state)
    i, a, mult = _rglru_gates(p, x_c)
    h_state = a[:, 0] * h_state + (i[:, 0] * x_c.astype(jnp.float32)[:, 0]) * mult[:, 0]
    out = h_state[:, None, :].astype(x_t.dtype) * yb
    return dense(out, p["linear_out"]), conv_state, h_state


def rglru_state_shapes(cfg: ArchConfig, batch: int):
    w = cfg.lru_width or cfg.d_model
    return ((batch, cfg.ssm_conv_kernel - 1, w),   # conv state
            (batch, w))                            # recurrent state (fp32)
