"""A dense GQA decoder (Qwen2, Granite 3.0): its layout, weights and
counts of useful work.

Layout (the serving program's parameter tree, which is its input
format): ``embed (V, d)``, ``ln_f.gamma (d,)``, ``lm_head (d, V)`` unless
embeddings are tied, and one stack ``layers``, every leaf stacked over
``L``: ``ln1.gamma``, ``ln2.gamma (d,)``; ``attn.wq (d, H, hd)``,
``attn.wk`` / ``attn.wv (d, Hk, hd)``, ``attn.wo (H, hd, d)``, biases
``attn.bq (H, hd)``, ``attn.bk`` / ``attn.bv (Hk, hd)`` when the config
has them; ``mlp.gate`` / ``mlp.up (d, f)`` and ``mlp.down (f, d)``.

Scales keep activations of order one at any depth: a matrix is normal
with variance ``1 / fan_in``, the embedding ``1 / d``, a norm's gain
``1 + 0.1 N(0, 1)`` and a bias ``0.5 N(0, 1)``, so norms and biases are
not identities that a fault could drop unseen.

Counts follow ``harness.roofline``: every layer reads its matrices
(attention ``d (H + 2 Hk) hd + H hd d``, MLP ``3 d f``) and its KV
(``2 Hk hd`` a key), and runs one paged attention kernel call.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Iterable, List, Tuple

import jax

from chipbench.harness.weights import fold, gain, normal

STACK = "layers"


def arch_config(a: Dict):
    """The program's ``ArchConfig``: the sizes it has fields for."""
    from repro.configs.base import ArchConfig
    fields = {f.name for f in dataclasses.fields(ArchConfig)}
    return ArchConfig(**{k: v for k, v in a.items() if k in fields})


def stacks(a: Dict) -> List[Tuple[str, int]]:
    return [(STACK, a["n_layers"])]


def layer_shapes(a: Dict) -> Dict:
    d, H, Hk, hd, f = (a["d_model"], a["n_heads"], a["n_kv_heads"],
                       a["head_dim"], a["d_ff"])
    attn = {"wq": (d, H, hd), "wk": (d, Hk, hd), "wv": (d, Hk, hd),
            "wo": (H, hd, d)}
    if a.get("qkv_bias"):
        attn.update(bq=(H, hd), bk=(Hk, hd), bv=(Hk, hd))
    return {"ln1": {"gamma": (d,)}, "ln2": {"gamma": (d,)}, "attn": attn,
            "mlp": {"gate": (d, f), "up": (d, f), "down": (f, d)}}


def _fan_in(name: str, shape) -> int:
    if name == "wo":
        return shape[0] * shape[1]
    return shape[0]


def layer_weights(key, a: Dict, stack: str, layer) -> Dict:
    """One decoder layer's weights; ``layer`` may be traced."""
    k = jax.random.fold_in(fold(key, stack), layer)
    out: Dict = {}
    for group, leaves in layer_shapes(a).items():
        out[group] = {}
        for name, shape in leaves.items():
            tag = f"{group}.{name}"
            if name == "gamma":
                out[group][name] = gain(k, tag, shape)
            elif name.startswith("b"):
                out[group][name] = normal(k, tag, shape, 0.5)
            else:
                out[group][name] = normal(k, tag, shape,
                                          _fan_in(name, shape) ** -0.5)
    return out


def outer_weights(key, a: Dict) -> Dict:
    """Embedding, final norm and (untied) LM head."""
    d, V = a["d_model"], a["vocab_size"]
    out = {"embed": normal(key, "embed", (V, d), d ** -0.5),
           "ln_f": {"gamma": gain(key, "ln_f", (d,))}}
    if not a.get("tie_embeddings"):
        out["lm_head"] = normal(key, "lm_head", (d, V), d ** -0.5)
    return out


def _geom(a: Dict) -> Tuple[int, int, int]:
    Hk = a["n_kv_heads"]
    return Hk, a["n_heads"] // Hk, a["head_dim"]


def paged_decode(a: Dict, contexts: Iterable[int], kv_bytes: int = 2,
                 act_bytes: int = 2) -> Tuple[float, float]:
    """One call of the paged decode kernel (one layer, one token per
    slot); ``contexts`` are the keys each active slot attends, the new
    one included.  Returns ``(flops, bytes)``."""
    Hk, G, d = _geom(a)
    ctx = list(contexts)
    keys = sum(ctx)
    flops = 4.0 * Hk * G * d * keys
    nbytes = 2.0 * Hk * d * kv_bytes * keys + 2.0 * len(ctx) * Hk * G * d \
        * act_bytes
    return flops, nbytes


def paged_prefill(a: Dict, start: int, valid: int, kv_bytes: int = 2,
                  act_bytes: int = 2) -> Tuple[float, float]:
    """One call of the paged prefill kernel: ``valid`` queries of one
    slot at absolute positions ``start ..``, each attending the keys up to
    its own position."""
    Hk, G, d = _geom(a)
    pairs = valid * start + valid * (valid + 1) / 2.0
    flops = 4.0 * Hk * G * d * pairs
    nbytes = 2.0 * Hk * d * kv_bytes * (start + valid) \
        + 2.0 * valid * Hk * G * d * act_bytes
    return flops, nbytes


KERNELS = {"paged_decode": paged_decode, "paged_prefill": paged_prefill}


def kernel_calls(a: Dict, kernel: str, *work, kv_bytes: int = 2
                 ) -> List[Tuple[int, float, float]]:
    """One call of ``kernel`` in every layer, each of the same counts."""
    one = KERNELS.get(kernel)
    if one is None:
        return []
    return [(a["n_layers"], *one(a, *work, kv_bytes))]


def layer_matmul_params(a: Dict) -> int:
    d, H, Hk, hd, f = (a["d_model"], a["n_heads"], a["n_kv_heads"],
                       a["head_dim"], a["d_ff"])
    return d * (H + 2 * Hk) * hd + H * hd * d + 3 * d * f


def _kv_token_bytes(a: Dict, kv_bytes: int) -> float:
    return 2.0 * a["n_kv_heads"] * a["head_dim"] * kv_bytes


def prefill_chunk(a: Dict, start: int, valid: int, last: bool,
                  kv_bytes: int = 2, w_bytes: int = 2) -> Tuple[float, float]:
    """One chunk of one prompt through the whole model: every layer's
    weights and the slot's keys read, the chunk's keys written, and the
    LM head for one row when the chunk produces the first token."""
    L, d, V = a["n_layers"], a["d_model"], a["vocab_size"]
    P = layer_matmul_params(a)
    af, ab = paged_prefill(a, start, valid, kv_bytes)
    flops = L * (2.0 * P * valid + af)
    nbytes = L * (P * w_bytes + ab + _kv_token_bytes(a, kv_bytes) * valid) \
        + valid * d * w_bytes
    if last:
        flops += 2.0 * d * V
        nbytes += d * V * w_bytes
    return flops, nbytes


def decode_token_step(a: Dict, contexts: Iterable[int], kv_bytes: int = 2,
                      w_bytes: int = 2) -> Tuple[float, float]:
    """One token for each active slot through the whole model."""
    L, d, V = a["n_layers"], a["d_model"], a["vocab_size"]
    P = layer_matmul_params(a)
    ctx = list(contexts)
    S = len(ctx)
    af, ab = paged_decode(a, ctx, kv_bytes)
    flops = L * (2.0 * P * S + af) + 2.0 * d * V * S
    nbytes = L * (P * w_bytes + ab + _kv_token_bytes(a, kv_bytes) * S) \
        + d * V * w_bytes + S * d * w_bytes
    return flops, nbytes
