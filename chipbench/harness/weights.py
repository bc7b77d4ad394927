"""Seeded weights for a dense GQA decoder, made on the device.

The benchmark makes the weights itself, from ``--seed``, and hands them
to the system under test; the plain reference makes the same weights
again, layer by layer, after the system's state is freed.  Neither takes
anything from the other.

Layout (the serving program's parameter tree, which is its input
format): ``embed (V, d)``, ``ln_f.gamma (d,)``, ``lm_head (d, V)`` unless
embeddings are tied, and ``layers``, every leaf stacked over ``L``:
``ln1.gamma``, ``ln2.gamma (d,)``; ``attn.wq (d, H, hd)``, ``attn.wk`` /
``attn.wv (d, Hk, hd)``, ``attn.wo (H, hd, d)``, biases ``attn.bq (H, hd)``,
``attn.bk`` / ``attn.bv (Hk, hd)`` when the config has them;
``mlp.gate`` / ``mlp.up (d, f)`` and ``mlp.down (f, d)``.

Scales keep activations of order one at any depth: a matrix is normal
with variance ``1 / fan_in``, the embedding ``1 / d``, a norm's gain
``1 + 0.1 N(0, 1)`` and a bias ``0.5 N(0, 1)``, so norms and biases are
not identities that a fault could drop unseen.  Values are drawn in the
served dtype (bf16) directly.
"""
from __future__ import annotations

import zlib
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

DTYPE = jnp.bfloat16


def base_key(seed: int) -> jax.Array:
    """A PRNG key from any whole-number seed (wider than 32 bits too)."""
    words = np.random.SeedSequence(int(seed)).generate_state(2)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32),
                                    impl="threefry2x32")


def _key(key, name: str):
    return jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)


def _normal(key, name, shape, std):
    return (jax.random.normal(_key(key, name), shape, DTYPE)
            * jnp.asarray(std, DTYPE))


def _gain(key, name, shape):
    return (1.0 + 0.1 * jax.random.normal(_key(key, name), shape,
                                          jnp.float32)).astype(DTYPE)


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


def layer_weights(key, a: Dict, layer) -> Dict:
    """One decoder layer's weights; ``layer`` may be traced."""
    k = jax.random.fold_in(_key(key, "layers"), layer)
    out: Dict = {}
    for group, leaves in layer_shapes(a).items():
        out[group] = {}
        for name, shape in leaves.items():
            tag = f"{group}.{name}"
            if name == "gamma":
                out[group][name] = _gain(k, tag, shape)
            elif name.startswith("b"):
                out[group][name] = _normal(k, tag, shape, 0.5)
            else:
                out[group][name] = _normal(k, tag, shape,
                                           _fan_in(name, shape) ** -0.5)
    return out


def outer_weights(key, a: Dict) -> Dict:
    """Embedding, final norm and (untied) LM head."""
    d, V = a["d_model"], a["vocab_size"]
    out = {"embed": _normal(key, "embed", (V, d), d ** -0.5),
           "ln_f": {"gamma": _gain(key, "ln_f", (d,))}}
    if not a.get("tie_embeddings"):
        out["lm_head"] = _normal(key, "lm_head", (d, V), d ** -0.5)
    return out


def make_params(a: Dict, seed: int, sharding=None) -> Dict:
    """Every weight, in one jitted call on the device.  Layers are made
    one after another inside it (``lax.map``), so the temporaries of only
    one layer exist at a time."""
    def make(key):
        p = outer_weights(key, a)
        p["layers"] = jax.lax.map(lambda i: layer_weights(key, a, i),
                                  jnp.arange(a["n_layers"]))
        return p
    return jax.block_until_ready(
        jax.jit(make, out_shardings=sharding)(base_key(seed)))


def param_bytes(a: Dict) -> int:
    n = a["vocab_size"] * a["d_model"] * (1 if a.get("tie_embeddings")
                                          else 2) + a["d_model"]
    for leaves in layer_shapes(a).values():
        for shape in leaves.values():
            n += a["n_layers"] * int(np.prod(shape))
    return n * jnp.dtype(DTYPE).itemsize
