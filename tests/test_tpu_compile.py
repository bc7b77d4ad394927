"""Compile-only checks of the engine's Pallas kernels for a TPU v5e.

The TPU compiler compiles for a chip that is described, not attached, so
these tests catch what interpret mode cannot (block shapes Mosaic
refuses, VMEM overruns) without a chip.  Shapes are qwen2-7b's published
widths: 4 KV heads with a query group of 7, head_dim 128, 16-token KV
blocks, d_model 3584 and d_ff 18944.  The last test compiles the engine's
whole prefill and decode steps and checks the names the benchmark's
trace reduction finds them by.
"""
import dataclasses
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.grouped_lora.grouped_lora import grouped_lora_fwd
from repro.kernels.paged_attention.paged_attention import (
    paged_decode_fwd, paged_prefill_fwd, paged_verify_fwd)

HK, G, D, BS = 4, 7, 128, 16          # qwen2-7b attention geometry
N_BLOCKS, MAX_BPS, SLOTS = 512, 64, 8  # a 512-block pool, 1024-token tables
CHUNK, VERIFY_Q = 256, 5
D_MODEL, D_FF = 3584, 18944


@pytest.fixture(scope="module")
def one_chip():
    """One device of a described v5e host.  Describing the topology loads
    the TPU compiler library, so it happens here, inside a test, never
    while a module is imported."""
    import os
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:          # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described chip can write the persistent cache but not read it
    # back; keep these compiles out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


KV_DTYPES = [jnp.bfloat16, jnp.int8]


@pytest.mark.parametrize("kv_dtype", KV_DTYPES, ids=["bf16", "int8"])
def test_paged_decode_compiles(one_chip, kv_dtype):
    pool = _sds((N_BLOCKS, HK, BS, D), kv_dtype, one_chip)
    _compile(paged_decode_fwd,
             _sds((SLOTS, HK, G, D), jnp.bfloat16, one_chip), pool, pool,
             _sds((SLOTS, MAX_BPS), jnp.int32, one_chip),
             _sds((SLOTS,), jnp.int32, one_chip))


#: the benchmark cells' decode geometry at 128-token pages:
#: (slots, KV heads, query group, head_dim, pool blocks, table width)
CELL_DECODE = {"qwen2-7b-l20": (16, 4, 7, 128, 320, 36),
               "granite-3-2b": (8, 8, 4, 64, 208, 32)}


@pytest.mark.parametrize("kv_dtype", KV_DTYPES, ids=["bf16", "int8"])
@pytest.mark.parametrize("cell", CELL_DECODE)
def test_paged_decode_compiles_at_cell_geometry(one_chip, cell, kv_dtype):
    """Mosaic takes the decode kernel's blocks of several pages of every
    KV head, and their double buffers fit VMEM, at the cells' sizes."""
    S, hk, g, d, n, max_bps = CELL_DECODE[cell]
    pool = _sds((n, hk, 128, d), kv_dtype, one_chip)
    _compile(paged_decode_fwd,
             _sds((S, hk, g, d), jnp.bfloat16, one_chip), pool, pool,
             _sds((S, max_bps), jnp.int32, one_chip),
             _sds((S,), jnp.int32, one_chip))


@pytest.mark.parametrize("kv_dtype", KV_DTYPES, ids=["bf16", "int8"])
def test_paged_prefill_compiles(one_chip, kv_dtype):
    pool = _sds((N_BLOCKS, HK, BS, D), kv_dtype, one_chip)
    scalar = _sds((), jnp.int32, one_chip)
    _compile(paged_prefill_fwd,
             _sds((CHUNK, HK, G, D), jnp.bfloat16, one_chip), pool, pool,
             _sds((MAX_BPS,), jnp.int32, one_chip), scalar, scalar)


@pytest.mark.parametrize("kv_dtype", KV_DTYPES, ids=["bf16", "int8"])
def test_paged_verify_compiles(one_chip, kv_dtype):
    pool = _sds((N_BLOCKS, HK, BS, D), kv_dtype, one_chip)
    _compile(paged_verify_fwd,
             _sds((SLOTS, VERIFY_Q, HK, G, D), jnp.bfloat16, one_chip),
             pool, pool, _sds((SLOTS, MAX_BPS), jnp.int32, one_chip),
             _sds((SLOTS,), jnp.int32, one_chip))


@pytest.mark.parametrize("tokens,n,rank", [(1, D_MODEL, 16),
                                           (CHUNK, D_FF, 64)],
                         ids=["q_proj-decode", "mlp-prefill"])
def test_grouped_lora_compiles(one_chip, tokens, n, rank):
    pool_slots = 8
    _compile(grouped_lora_fwd,
             _sds((SLOTS, tokens, D_MODEL), jnp.bfloat16, one_chip),
             _sds((pool_slots, D_MODEL, rank), jnp.bfloat16, one_chip),
             _sds((pool_slots, rank, n), jnp.bfloat16, one_chip),
             _sds((SLOTS,), jnp.int32, one_chip))


def test_engine_steps_keep_the_names_the_trace_reduction_reads(
        one_chip, monkeypatch):
    """The benchmark finds the engine's programs in a device trace by
    module name (``jit_prefill``, ``jit_decode``) and the paged kernels by
    the start of their HLO instruction names
    (``chipbench/harness/xtrace.py``).  Compile both steps as a cell runs
    them (paged attention, 128-token blocks, 256-token chunks, 8 fused
    decode steps; two layers at published widths) and find those names."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec
    from chipbench.harness import xtrace
    from repro import configs
    from repro.engine.decode_loop import make_engine_fns
    from repro.engine.kv_cache import BlockPagedKVCache
    from repro.kernels.paged_attention import ops as paged_ops
    from repro.models import init_params
    from repro.runtime import ShardingPolicy

    # the kernels choose interpret mode from the backend, which is the CPU
    # here; compile them as the chip would, and drop traces made for the CPU
    monkeypatch.setattr(paged_ops, "_on_cpu", lambda: False)
    jax.clear_caches()
    cfg = dataclasses.replace(configs.get("qwen2-7b"), n_layers=2)
    slots, bs, chunk, n_steps = 4, 128, CHUNK, 8
    mesh = Mesh(np.array(list(one_chip.device_set)).reshape(1, 1),
                ("data", "model"))
    cache = BlockPagedKVCache(cfg, slots, n_blocks=16, block_size=bs,
                              max_blocks_per_seq=4, kv_dtype="bf16")
    prefill_fn, decode_fn, sh = make_engine_fns(
        cfg, mesh, ShardingPolicy(), cache, chunk_size=chunk,
        decode_block=n_steps, attn_impl="paged")

    def shapes(tree, shardings):
        return jax.tree.map(lambda a, s: _sds(a.shape, a.dtype, s), tree,
                            shardings)

    params = shapes(jax.eval_shape(
        lambda: init_params(cfg, jax.random.PRNGKey(0))), sh["params"])
    state = shapes(jax.eval_shape(cache.init_state), sh["state"])
    rep = NamedSharding(mesh, PartitionSpec())
    key = jax.eval_shape(jax.random.PRNGKey, 0)
    scalar = _sds((), jnp.int32, rep)
    steps = {
        "prefill": prefill_fn.lower(
            params, state, _sds((1, chunk), jnp.int32, rep), scalar, scalar,
            scalar),
        "decode": decode_fn.lower(
            params, state, _sds((slots,), jnp.bool_, rep),
            _sds((slots,), jnp.int32, rep), _sds(key.shape, key.dtype, rep)),
    }
    for step, lowered in steps.items():
        hlo = lowered.compile().as_text()
        assert hlo.startswith(f"HloModule jit_{step},"), hlo[:80]
        kernel = xtrace.KERNEL_PATTERNS[f"paged_{step}"]
        calls = re.findall(rf"%({kernel}[.\w]*) = \S+ custom-call\(", hlo)
        assert calls, f"no {kernel} custom call in jit_{step}"
