"""A configuration names its architecture, and the harness asks that
module alone for the program's config, the weights and the counts.

The stand-in architecture here is defined by the test and named by no
harness file.  It delegates to the dense one, records what it was asked,
keeps its attention sizes in a nested group of the configuration, and
lays the layers out as two stacks (the first layer, and the rest), as an
architecture whose leading layers differ would.  A stand-in program
joins the two stacks into the dense program's one.  The profiler, which
needs the chip, is replaced by the hand-made trace of ``test_xtrace``, so
the whole traced path of ``run.run_cell`` runs on the CPU."""
from __future__ import annotations

import json
import re
import sys
import types

import jax
import jax.numpy as jnp
import pytest

from chipbench import run
from chipbench.arch import dense
from chipbench.harness import spec, weights as W, xtrace
from chipbench.reference import dense as dense_ref
from chipbench.tests import test_xtrace as T
from chipbench.tests import tiny

NAME = "standin_arch"
NESTED = ("n_heads", "n_kv_heads", "head_dim", "qkv_bias", "rope_theta")
SEED = 2**31 + 977


def _flat(a):
    a = dict(a)
    return dict(a, **a.pop("attention"))


def _standin(asked):
    """The stand-in architecture and its reference, as modules."""
    arch = types.ModuleType(f"chipbench.arch.{NAME}")

    def asks(name, fn):
        def call(*args, **kw):
            asked.append(name)
            return fn(*args, **kw)
        setattr(arch, name, call)

    def layer_weights(key, a, stack, i):
        first = {"first": 0, "rest": 1}[stack]
        return dense.layer_weights(key, _flat(a), "layers", i + first)

    asks("arch_config", lambda a: dense.arch_config(_flat(a)))
    asks("stacks", lambda a: [("first", 1), ("rest", a["n_layers"] - 1)])
    asks("layer_weights", layer_weights)
    asks("outer_weights", lambda key, a: dense.outer_weights(key, _flat(a)))
    asks("prefill_chunk", lambda a, *w: dense.prefill_chunk(_flat(a), *w))
    asks("decode_token_step",
         lambda a, *w: dense.decode_token_step(_flat(a), *w))
    asks("kernel_calls", lambda a, k, *w, **kw: dense.kernel_calls(
        _flat(a), k, *w, **kw))

    ref = types.ModuleType(f"chipbench.reference.{NAME}")
    ref.score = lambda a, *args, **kw: dense_ref.score(_flat(a), *args, **kw)
    ref.gaps = dense_ref.gaps
    return arch, ref


def joining(engine):
    """A program whose layout has the two stacks: it joins them and serves
    them with the dense ``engine``."""
    def make(cfg, params, *args, **kw):
        params = dict(params)
        first, rest = params.pop("first"), params.pop("rest")
        params["layers"] = jax.tree_util.tree_map(
            lambda x, y: jnp.concatenate([x, y]), first, rest)
        return engine(cfg, params, *args, **kw)
    return make


@pytest.fixture
def standin(monkeypatch, tmp_path_factory):
    asked = []
    arch, ref = _standin(asked)
    monkeypatch.setitem(sys.modules, arch.__name__, arch)
    monkeypatch.setitem(sys.modules, ref.__name__, ref)
    monkeypatch.setattr(run, "CACHE_DIR",
                        tmp_path_factory.getbasetemp() / "jax_cache")
    c = tiny.cell("tiny-open")
    a = {k: v for k, v in c.arch.items() if k not in NESTED}
    a["attention"] = {k: c.arch[k] for k in NESTED}
    c.config = dict(c.config, reference=NAME, arch=a)
    return c, arch, asked


def test_standin_layout_is_the_dense_weights(standin):
    c, arch, _ = standin
    assert c.architecture is arch
    p = W.make_params(arch, c.arch, SEED)
    want = W.make_params(dense, tiny.cell().arch, SEED)
    for stack, lo, hi in (("first", 0, 1), ("rest", 1, None)):
        for got, ref in zip(jax.tree_util.tree_leaves(p[stack]),
                            jax.tree_util.tree_leaves(want["layers"])):
            assert (got == ref[lo:hi]).all()
    assert W.param_bytes(arch, c.arch) == W.param_bytes(
        dense, tiny.cell().arch)


def test_run_cell_asks_the_configurations_architecture(
        standin, monkeypatch, tmp_path, capsys):
    import repro.engine
    c, _, asked = standin
    monkeypatch.setattr(repro.engine, "Engine",
                        joining(repro.engine.Engine))
    monkeypatch.setattr(jax.profiler, "start_trace", lambda d: None)
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: None)
    (tmp_path / "chip.xplane.pb").write_bytes(b"")
    from jax.profiler import ProfileData
    monkeypatch.setattr(xtrace, "load", lambda path: (
        ProfileData.from_text_proto(T.trace_text())))
    r = run.run_cell(c, SEED, 2.0, True, peaks=tiny.PEAKS,
                     trace_dir=tmp_path)
    assert r["correct"], r["check"]
    assert "step_mfu" in r["metrics"]
    assert {"arch_config", "stacks", "layer_weights", "outer_weights",
            "prefill_chunk", "decode_token_step"} <= set(asked)
    setup = next(json.loads(line.split(" ", 1)[1]) for line in
                 capsys.readouterr().out.splitlines()
                 if line.startswith("[setup]"))
    assert setup["weight_bytes"] == W.param_bytes(dense, tiny.cell().arch)
    # the harness names no architecture, and no dense leaf or size
    for path in [spec.BENCH_DIR / "run.py", *sorted(
            (spec.BENCH_DIR / "harness").glob("*.py")), *sorted(
            (spec.BENCH_DIR / "metrics").glob("*.py"))]:
        text = path.read_text()
        assert NAME not in text, path
        assert not re.search(r"\bwq\b|d_ff|n_kv_heads|head_dim|n_layers",
                             text), path
