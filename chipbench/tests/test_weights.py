"""The weights that the program gets and that the reference makes again
are the same numbers."""
from __future__ import annotations

import json

import jax
import numpy as np

from chipbench.arch import dense
from chipbench.harness import spec, weights as W
from chipbench.tests import tiny


def test_stacked_layers_equal_layer_by_layer():
    a = tiny.cell().arch
    seed = 2**31 + 5
    p = W.make_params(dense, a, seed)
    key = W.base_key(seed)
    for i in range(a["n_layers"]):
        one = jax.jit(lambda k, j: dense.layer_weights(k, a, "layers", j))(
            key, i)
        flat = jax.tree_util.tree_leaves_with_path(one)
        for path, leaf in flat:
            stacked = p["layers"]
            for k in path:
                stacked = stacked[k.key]
            assert np.array_equal(np.asarray(stacked[i]), np.asarray(leaf))


def test_seeds_differ_and_repeat():
    a = tiny.cell().arch
    x = W.make_params(dense, a, 1)["embed"]
    y = W.make_params(dense, a, 2)["embed"]
    z = W.make_params(dense, a, 1)["embed"]
    assert not np.array_equal(np.asarray(x), np.asarray(y))
    assert np.array_equal(np.asarray(x), np.asarray(z))


def test_layout_is_the_programs():
    from repro.models import abstract_params
    for name in ("qwen2-7b-l20", "granite-3-2b"):
        a = json.loads((spec.BENCH_DIR / "configs" / f"{name}.json")
                       .read_text())["arch"]
        want = abstract_params(dense.arch_config(a))
        got = jax.eval_shape(lambda: _abstract(a))
        assert jax.tree_util.tree_structure(got) == \
            jax.tree_util.tree_structure(want)
        for g, w in zip(jax.tree_util.tree_leaves(got),
                        jax.tree_util.tree_leaves(want)):
            assert (g.shape, g.dtype) == (w.shape, w.dtype)
        assert W.param_bytes(dense, a) == sum(
            x.size * x.dtype.itemsize for x in jax.tree_util.tree_leaves(want))


def _abstract(a):
    key = W.base_key(0)
    p = dense.outer_weights(key, a)
    p["layers"] = jax.lax.map(lambda i: dense.layer_weights(key, a, "layers",
                                                            i),
                              jax.numpy.arange(a["n_layers"]))
    return p
