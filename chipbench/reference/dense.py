"""Plain reference for a dense GQA decoder, in float32, in blocks.

The architecture, as published for Qwen2 (arXiv:2407.10671) and IBM
Granite 3.0, with the departures that the configuration file lists:

    x_0 = E[t]
    per layer:  h = rms(x) * g1
                q = h Wq + bq,  k = h Wk + bk,  v = h Wv + bv   (bias: Qwen2)
                q, k <- RoPE(positions, theta), rotating halves [x1, x2]
                query head j reads KV head j // (H / Hk)
                a = softmax(q k^T / sqrt(hd), causal) v
                x = x + a Wo
                x = x + (silu(rms(x) * g2 Wg) * (rms(x) * g2 Wu)) Wd
    logits = rms(x_L) * g_f  @  W_head   (tied: E^T)
    rms(x) = x / sqrt(mean(x^2) + eps)

It imports nothing of the serving program.  It runs after the program's
state is freed: weights are made again from the seed one layer at a
time (``chipbench/arch/dense.py``), and the whole sequence goes through one
layer per call, so only one layer's weights and one sequence's
activations exist at a time.  Matrix products run at ``highest``
precision, every activation is float32.

``mode="fp8"`` is the control: the same forward pass with the operands
of every linear layer rounded to float8 (e4m3) with a scale per output
channel (weights) and per token (activations), the step below the
bfloat16 that the configurations state.
"""
from __future__ import annotations

import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.arch import dense as dense_arch
from chipbench.harness import weights as W

F8 = jnp.float8_e4m3fn
F8_MAX = 448.0
ROW_BLOCK = 256           # logits rows per block


def _q8(x, axis):
    """Round to e4m3 with one scale per slice along ``axis``."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / F8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(F8).astype(jnp.float32) * s


def _mm(x, w, spec, mode, w_axes):
    """``einsum(spec, x, w)`` in f32; fp8 mode rounds both operands."""
    w = w.astype(jnp.float32)
    if mode == "fp8":
        x = _q8(x, -1)
        w = _q8(w, w_axes)
    return jnp.einsum(spec, x, w, precision="highest")


def _rms(x, g, eps):
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * g.astype(jnp.float32)


def _rope(x, theta):
    """x: (T, heads, hd) at positions 0..T-1."""
    T, hd = x.shape[0], x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnames=("a", "mode"))
def _layer(x, p, a, mode):
    a = dict(a)
    T = x.shape[0]
    H, Hk, hd = a["n_heads"], a["n_kv_heads"], a["head_dim"]
    G = H // Hk
    eps = a["norm_eps"]
    h = _rms(x, p["ln1"]["gamma"], eps)
    pa = p["attn"]
    q = _mm(h, pa["wq"], "td,dhk->thk", mode, 0)
    k = _mm(h, pa["wk"], "td,dhk->thk", mode, 0)
    v = _mm(h, pa["wv"], "td,dhk->thk", mode, 0)
    if "bq" in pa:
        q = q + pa["bq"].astype(jnp.float32)
        k = k + pa["bk"].astype(jnp.float32)
        v = v + pa["bv"].astype(jnp.float32)
    q = _rope(q, a["rope_theta"]).reshape(T, Hk, G, hd)
    k = _rope(k, a["rope_theta"])
    causal = jnp.arange(T)[None, :] <= jnp.arange(T)[:, None]

    def one_kv_head(j):
        qj = q[:, j]                                     # (T, G, hd)
        s = jnp.einsum("tgd,sd->gts", qj, k[:, j],
                       precision="highest") * hd ** -0.5
        s = jnp.where(causal[None], s, -jnp.inf)
        pr = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("gts,sd->tgd", pr, v[:, j], precision="highest")

    att = jax.lax.map(one_kv_head, jnp.arange(Hk))       # (Hk, T, G, hd)
    att = jnp.transpose(att, (1, 0, 2, 3)).reshape(T, H, hd)
    x = x + _mm(att, pa["wo"], "thk,hkd->td", mode, (0, 1))
    h = _rms(x, p["ln2"]["gamma"], eps)
    pm = p["mlp"]
    g = _mm(h, pm["gate"], "td,df->tf", mode, 0)
    u = _mm(h, pm["up"], "td,df->tf", mode, 0)
    return x + _mm(jax.nn.silu(g) * u, pm["down"], "tf,fd->td", mode, 0)


@functools.partial(jax.jit, static_argnames=("a",))
def _make_layer(key, i, a):
    return dense_arch.layer_weights(key, dict(a), dense_arch.STACK, i)


@functools.partial(jax.jit, static_argnames=("a",))
def _make_outer(key, a):
    return dense_arch.outer_weights(key, dict(a))


@functools.partial(jax.jit, static_argnames=("a", "mode"))
def _logits_block(h, outer, a, mode):
    a = dict(a)
    h = _rms(h, outer["ln_f"]["gamma"], a["norm_eps"])
    if a.get("tie_embeddings"):
        return _mm(h, outer["embed"], "td,vd->tv", mode, 1)
    return _mm(h, outer["lm_head"], "td,dv->tv", mode, 0)


def _hidden(tokens, key, outer, a, mode):
    x = outer["embed"][tokens].astype(jnp.float32)
    for i in range(dict(a)["n_layers"]):
        x = _layer(x, _make_layer(key, jnp.int32(i), a), a, mode)
    return x


def score(arch: Dict, seed: int, tokens: np.ndarray, targets: np.ndarray,
          control: bool = False) -> Dict[str, np.ndarray]:
    """Score one sequence.  ``tokens (T,)`` is a prompt and its served
    tokens, right-padded to a fixed ``T`` (causal: padding reaches no
    earlier row); ``targets[t]`` is the token served after row ``t``
    (``-1`` where none).  Returns per row ``best`` (the float32
    reference's largest logit) and ``at_target`` (its logit of the
    target), and with ``control`` also ``at_control`` (its logit of the
    token that the fp8 pass ranks first)."""
    a = tuple(sorted(arch.items()))
    key = W.base_key(seed)
    outer = _make_outer(key, a)
    tok = jnp.asarray(tokens, jnp.int32)
    h = _hidden(tok, key, outer, a, "f32")
    h8 = _hidden(tok, key, outer, a, "fp8") if control else None
    tg = jnp.asarray(np.maximum(targets, 0), jnp.int32)
    best, at_t, at_c = [], [], []
    for r in range(0, len(tokens), ROW_BLOCK):
        sl = slice(r, r + ROW_BLOCK)
        lg = _logits_block(h[sl], outer, a, "f32")
        best.append(jnp.max(lg, axis=-1))
        at_t.append(jnp.take_along_axis(lg, tg[sl, None], axis=-1)[:, 0])
        if control:
            pick = jnp.argmax(_logits_block(h8[sl], outer, a, "fp8"), -1)
            at_c.append(jnp.take_along_axis(lg, pick[:, None], -1)[:, 0])
    out = {"best": np.asarray(jnp.concatenate(best)),
           "at_target": np.asarray(jnp.concatenate(at_t))}
    if control:
        out["at_control"] = np.asarray(jnp.concatenate(at_c))
    return out


def gaps(sc: Dict[str, np.ndarray], rows: np.ndarray) -> Tuple[float, ...]:
    """Widest gap below the reference's best logit, over ``rows``, of the
    served token and (when scored) of the control's token."""
    g = float(np.max(sc["best"][rows] - sc["at_target"][rows]))
    if "at_control" in sc:
        return g, float(np.max(sc["best"][rows] - sc["at_control"][rows]))
    return (g,)
