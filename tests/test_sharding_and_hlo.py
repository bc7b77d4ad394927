"""Sharding-rule resolution + HLO cost-analyzer validation."""
import types

import jax
import jax.numpy as jnp
import pytest

from repro.core import hlo
from repro.runtime.sharding import ShardingPolicy, spec_for
from repro import configs


def _mesh_stub(**shape):
    return types.SimpleNamespace(shape=shape)


POL = ShardingPolicy(dp_axes=("data",), tp_axis="model")
POL_POD = ShardingPolicy(dp_axes=("pod", "data"), tp_axis="model",
                         fsdp=True)
MESH = _mesh_stub(data=16, model=16)
MESH_POD = _mesh_stub(pod=2, data=16, model=16)


def test_tp_axes_resolve():
    s = spec_for(("embed", "mlp"), (4096, 16384), MESH, POL)
    assert s == jax.sharding.PartitionSpec(None, "model")
    s = spec_for(("vocab", "embed"), (128256, 4096), MESH, POL)
    assert s == jax.sharding.PartitionSpec("model", None)


def test_divisibility_fallback_replicates():
    # kv_heads=2 can't shard over model=16 -> replicated
    s = spec_for(("embed", "kv_heads", None), (4096, 2, 128), MESH, POL)
    assert s == jax.sharding.PartitionSpec(None, None, None)


def test_kv_len_fallback_when_heads_fail():
    # cache (layers, batch, kv_len, kv_heads, hd): heads 8 fails on 16,
    # kv_len 32768 takes the model axis instead (sequence sharding)
    s = spec_for((None, "batch", "kv_len", "kv_heads", None),
                 (40, 128, 32768, 8, 64), MESH, POL)
    assert s == jax.sharding.PartitionSpec(None, "data", "model", None, None)


def test_fsdp_embed_sharding_multi_pod():
    s = spec_for(("embed", "mlp"), (16384, 53248), MESH_POD, POL_POD)
    assert s == jax.sharding.PartitionSpec(("pod", "data"), "model")


def test_batch_combined_dp_axes():
    s = spec_for(("batch", "seq"), (256, 4096), MESH_POD, POL_POD)
    assert s == jax.sharding.PartitionSpec(("pod", "data"), None)


def test_no_mesh_axis_used_twice():
    # heads takes model; mlp in the same tensor must not reuse it
    s = spec_for(("heads", "mlp"), (32, 16384), MESH, POL)
    used = [a for a in s if a is not None]
    assert len(used) == len(set(used)) == 1


@pytest.mark.parametrize("arch", configs.ASSIGNED)
def test_param_specs_resolve_for_all_archs(arch):
    """Every param's logical axes resolve on the production mesh shape."""
    from repro import models
    cfg = configs.get(arch)
    axes = jax.tree_util.tree_leaves(
        models.logical_axes(cfg),
        is_leaf=lambda x: isinstance(x, tuple) and all(
            isinstance(e, (str, type(None))) for e in x))
    shapes = jax.tree_util.tree_leaves(models.abstract_params(cfg))
    for ax, sds in zip(axes, shapes):
        spec = spec_for(tuple(ax), tuple(sds.shape), MESH_POD, POL_POD)
        # divisibility guaranteed by construction
        for dim, a in zip(sds.shape, spec):
            if a is not None:
                n = 1
                for x in (a if isinstance(a, tuple) else (a,)):
                    n *= MESH_POD.shape[x]
                assert dim % n == 0


# ---------------------------------------------------------------------------
# HLO analyzer
# ---------------------------------------------------------------------------



def test_analyzer_matches_cost_analysis_loop_free():
    def f(x, w1, w2):
        h = jax.nn.relu(x @ w1)
        return jax.nn.softmax((h @ w2).astype(jnp.float32), axis=-1)

    x = jnp.ones((128, 256), jnp.float32)
    w1 = jnp.ones((256, 512), jnp.float32)
    w2 = jnp.ones((512, 256), jnp.float32)
    comp = jax.jit(f).lower(x, w1, w2).compile()
    cost = comp.cost_analysis()
    mine = hlo.analyze(comp.as_text(), 1)
    assert mine.flops == pytest.approx(cost["flops"], rel=0.1)
    assert mine.unknown_trip_loops == 0


def test_analyzer_folds_scan_trip_counts():
    def g(x, w):
        def body(c, _):
            return jnp.tanh(c @ w), None
        out, _ = jax.lax.scan(body, x, None, length=5)
        return out

    x = jnp.ones((64, 64), jnp.float32)
    w = jnp.ones((64, 64), jnp.float32)
    comp = jax.jit(g).lower(x, w).compile()
    cost = comp.cost_analysis()
    mine = hlo.analyze(comp.as_text(), 1)
    # XLA counts the body once; we fold x5 (plus small outside-loop cost)
    assert 4.0 < mine.flops / cost["flops"] < 5.5


def test_collective_wire_conventions_synthetic():
    txt = """
HloModule m

ENTRY %main (a: f32[128,128]) -> f32[128,128] {
  %a = f32[128,128]{1,0} parameter(0)
  %ar = f32[128,128]{1,0} all-reduce(%a), replica_groups=[2,4]<=[8], to_apply=%add
  %ag = f32[128,128]{1,0} all-gather(%ar), replica_groups=[2,4]<=[8], dimensions={0}
  %rs = f32[128,128]{1,0} reduce-scatter(%ag), replica_groups=[2,4]<=[8], dimensions={0}, to_apply=%add
  ROOT %cp = f32[128,128]{1,0} collective-permute(%rs), source_target_pairs={{0,1}}
}
"""
    n = 128 * 128 * 4
    mc = hlo.analyze(txt, 8)
    assert mc.collective_wire["all-reduce"] == pytest.approx(n * 2 * 3 / 4)
    assert mc.collective_wire["all-gather"] == pytest.approx(n * 3 / 4)
    assert mc.collective_wire["reduce-scatter"] == pytest.approx(n * 3)
    assert mc.collective_wire["collective-permute"] == pytest.approx(n)


def test_dus_fusion_charged_as_inplace_update():
    txt = """
HloModule m

%fused_dus (p0: f32[100,1000], p1: f32[1,1000]) -> f32[100,1000] {
  %p0 = f32[100,1000]{1,0} parameter(0)
  %p1 = f32[1,1000]{1,0} parameter(1)
  %c = s32[] constant(3)
  ROOT %dus = f32[100,1000]{1,0} dynamic-update-slice(%p0, %p1, %c, %c)
}

ENTRY %main (buf: f32[100,1000], upd: f32[1,1000]) -> f32[100,1000] {
  %buf = f32[100,1000]{1,0} parameter(0)
  %upd = f32[1,1000]{1,0} parameter(1)
  ROOT %f = f32[100,1000]{1,0} fusion(%buf, %upd), kind=kLoop, calls=%fused_dus
}
"""
    mc = hlo.analyze(txt, 1)
    # charged 2x the 4KB update, NOT the 400KB buffer
    assert mc.bytes == pytest.approx(2 * 1000 * 4)


def test_annotated_shapes_still_match_collectives():
    """Layout/annotation-bearing shapes from newer XLA (tiled layouts
    ``{1,0:T(8,128)}``, memory-space suffixes ``S(1)``, ``maximal
    device=N`` sharding) must not drop collectives from the analyzer."""
    txt = """
HloModule m, entry_computation_layout={(f32[64,64]{1,0:T(8,128)S(1)})->f32[64,64]{1,0:T(8,128)}}

ENTRY %main (a: f32[64,64]) -> f32[64,64] {
  %a = f32[64,64]{1,0:T(8,128)S(1)} parameter(0), sharding={maximal device=0}
  %ar = f32[64,64]{1,0:T(8,128)} all-reduce(%a), replica_groups=[1,4]<=[4], to_apply=%add
  ROOT %ag = f32[64,64]{1,0:T(8,128)S(1)} all-gather(%ar), replica_groups=[1,4]<=[4], dimensions={0}
}
"""
    n = 64 * 64 * 4
    mc = hlo.analyze(txt, 4)
    assert mc.collective_counts["all-reduce"] == 1
    assert mc.collective_counts["all-gather"] == 1
    assert mc.collective_wire["all-reduce"] == pytest.approx(n * 2 * 3 / 4)
    assert mc.collective_wire["all-gather"] == pytest.approx(n * 3 / 4)


def test_collective_wire_elements_are_dtype_independent():
    """wire ELEMENTS must equal wire bytes / dtype width — the quantity
    the auditor renormalizes to the serving dtype (XLA:CPU widens bf16
    collectives to f32; raw byte comparison would be 2x off)."""
    tmpl = """
HloModule m

ENTRY %main (a: {dt}[128,128]) -> {dt}[128,128] {{
  %a = {dt}[128,128]{{1,0}} parameter(0)
  ROOT %ar = {dt}[128,128]{{1,0}} all-reduce(%a), replica_groups=[2,4]<=[8], to_apply=%add
}}
"""
    f32 = hlo.analyze(tmpl.format(dt="f32"), 8)
    bf16 = hlo.analyze(tmpl.format(dt="bf16"), 8)
    elems = 128 * 128 * 2 * 3 / 4          # ring all-reduce element count
    assert f32.wire_elements == pytest.approx(elems)
    assert bf16.wire_elements == pytest.approx(elems)
    assert f32.wire_bytes == pytest.approx(2 * bf16.wire_bytes)


def test_parse_input_output_aliases_header():
    txt = ("HloModule jit_step, input_output_alias={ {1,0}: (1, {0}, "
           "may-alias), {1,1}: (1, {1}, must-alias) }, "
           "entry_computation_layout={(f32[2,2]{1,0}, (f32[4,8,16,2,64]"
           "{4,3,2,1,0}, s32[2]{0}))->(f32[2,2], (f32[4,8,16,2,64], "
           "s32[2]))}\n\nENTRY %main () -> f32[] {}\n")
    aliases = hlo.parse_input_output_aliases(txt)
    assert len(aliases) == 2
    assert aliases[0].output_index == (1, 0)
    assert aliases[0].param_number == 1
    assert aliases[0].param_index == (0,)
    assert aliases[0].kind == "may-alias"
    assert aliases[1].kind == "must-alias"
    shapes = hlo.entry_parameter_shapes(txt)
    assert "f32[4,8,16,2,64]" in shapes     # rank-5 pool buffer survives
    assert shapes[0] == "f32[2,2]"


def test_no_aliases_parses_empty():
    assert hlo.parse_input_output_aliases("HloModule m\nENTRY e () {}") == []
