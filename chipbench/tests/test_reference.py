"""The plain reference computes the same model as the program's own
uncached forward pass (at a small size on the CPU, both in float32)."""
from __future__ import annotations


import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.arch import dense as dense_arch
from chipbench.harness import weights as W
from chipbench.reference import dense
from chipbench.tests import tiny


@pytest.mark.parametrize("tied", [False, True])
def test_reference_matches_program_forward(tied):
    from repro.models import forward
    a = dict(tiny.cell().arch, tie_embeddings=tied, qkv_bias=not tied)
    seed = 77
    T = 40
    toks = np.random.default_rng(0).integers(0, a["vocab_size"], T)
    p32 = jax.tree_util.tree_map(lambda x: x.astype(jnp.float32),
                                 W.make_params(dense_arch, a, seed))
    with jax.default_matmul_precision("highest"):
        want = np.asarray(forward(dense_arch.arch_config(a), p32,
                                  jnp.asarray(toks[None], jnp.int32))[0][0])
    targets = want.argmax(-1).astype(np.int32)
    sc = dense.score(a, seed, toks.astype(np.int32), targets)
    np.testing.assert_allclose(sc["best"], want.max(-1), rtol=2e-4,
                               atol=2e-4)
    np.testing.assert_allclose(sc["at_target"], sc["best"], atol=1e-6)
    # a wrong token is far below the best
    wrong = (targets + 1) % a["vocab_size"]
    sc2 = dense.score(a, seed, toks.astype(np.int32), wrong)
    assert dense.gaps(sc2, np.arange(T))[0] > 0.05


def test_control_rounds_below_bf16():
    a = tiny.cell().arch
    toks = np.random.default_rng(1).integers(0, a["vocab_size"], 48)
    sc = dense.score(a, 3, toks.astype(np.int32),
                     np.zeros(48, np.int32), control=True)
    g = dense.gaps(sc, np.arange(48))
    assert g[1] > 0.0                # fp8 picks another token somewhere
