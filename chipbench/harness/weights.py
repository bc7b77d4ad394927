"""Seeded weights, made on the device, in the layout that the
configuration's architecture module gives (``chipbench/arch``).

The benchmark makes the weights itself, from ``--seed``, and hands them
to the system under test; the plain reference makes the same weights
again, layer by layer, after the system's state is freed.  Neither takes
anything from the other.

Every leaf is drawn from a key folded from the seed's key by the leaf's
name (``fold``), in the served dtype (bf16) directly: ``normal`` for a
matrix, an embedding or a bias, ``gain`` for a norm's scale.  A layer of
a stack draws from the stack's key folded again by the layer's index.
"""
from __future__ import annotations

import functools
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


def fold(key, name: str):
    return jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)


def normal(key, name, shape, std):
    return (jax.random.normal(fold(key, name), shape, DTYPE)
            * jnp.asarray(std, DTYPE))


def gain(key, name, shape):
    """A norm's scale: ``1 + 0.1 N(0, 1)``."""
    return (1.0 + 0.1 * jax.random.normal(fold(key, name), shape,
                                          jnp.float32)).astype(DTYPE)


def _make(arch, a: Dict, key) -> Dict:
    p = arch.outer_weights(key, a)
    for stack, depth in arch.stacks(a):
        p[stack] = jax.lax.map(
            functools.partial(arch.layer_weights, key, a, stack),
            jnp.arange(depth))
    return p


def make_params(arch, a: Dict, seed: int, sharding=None) -> Dict:
    """Every weight, in one jitted call on the device.  The layers of each
    stack are made one after another inside it (``lax.map``), so the
    temporaries of only one layer exist at a time."""
    return jax.block_until_ready(
        jax.jit(functools.partial(_make, arch, a), out_shardings=sharding)(
            base_key(seed)))


def param_bytes(arch, a: Dict) -> int:
    """Bytes of every weight, from the shapes the layout makes."""
    shapes = jax.eval_shape(functools.partial(_make, arch, a), base_key(0))
    return sum(x.size * x.dtype.itemsize
               for x in jax.tree_util.tree_leaves(shapes))
