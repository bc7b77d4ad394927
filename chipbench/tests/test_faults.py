"""The check's control and its faults, on a cell small enough for the CPU.

Each test skips the harness's look for a chip and drives the rest of a
run (``run.run_cell``): weights from the seed, the engine, the open or
closed loop, and the check against the plain reference.  A sound run is
correct; the fp8 control lies past the limit; and with the timed path
broken underneath, ``correct`` comes out false once for each fault that a
serving cell can have.  (The faults of training and of several chips do
not apply to a one-chip serving cell.)"""
from __future__ import annotations

import jax.numpy as jnp
import pytest

from chipbench import run
from chipbench.tests import tiny

SEED = 2**31 + 4242


@pytest.fixture(autouse=True)
def _cache_outside_the_checkout(monkeypatch, tmp_path_factory):
    monkeypatch.setattr(run, "CACHE_DIR", tmp_path_factory.getbasetemp()
                        / "jax_cache")


def _run(mix="tiny-open", faults=None, control=False):
    return run.run_cell(tiny.cell(mix), SEED, 2.0, False, peaks=tiny.PEAKS,
                        faults=faults, control=control)


def alter_token(engine):
    """A token altered where it is produced: the first token of every
    decode block is replaced by the next id."""
    decode = engine.decode_fn
    V = engine.cfg.vocab_size

    def broken(*args):
        toks, produced, act, state = decode(*args)
        return toks.at[0].set((toks[0] + 1) % V), produced, act, state
    engine.decode_fn = broken


def state_unchanged(engine):
    """A step that returns its state unchanged: each decode block's keys
    and values are never written to the cache."""
    decode = engine.decode_fn

    def broken(params, state, *rest):
        keep = {k: jnp.copy(state[k]) for k in ("cache_k", "cache_v")}
        toks, produced, act, new = decode(params, state, *rest)
        return toks, produced, act, dict(new, **keep)
    engine.decode_fn = broken


@pytest.mark.parametrize("mix", ["tiny-open", "tiny-closed",
                                 "tiny-sessions"])
def test_sound_run_is_correct(mix):
    r = _run(mix)
    assert r["correct"], r["check"]
    assert r["attempted"] > 0 and r["failed"] == 0
    assert list(r)[-1] == "check"
    assert r["check"]["logit_gap"]["value"] <= r["check"]["logit_gap"][
        "limit"]


def test_control_fails_the_limit():
    r = _run(control=True)
    limit = r["check"]["logit_gap"]["limit"]
    assert r["check"]["logit_gap"]["value"] <= limit
    assert r["control_logit_gap"] > limit


@pytest.mark.parametrize("fault", [alter_token, state_unchanged])
def test_fault_is_not_correct(fault):
    r = _run(faults=fault)
    assert not r["correct"]
    assert r["check"]["logit_gap"]["value"] > r["check"]["logit_gap"][
        "limit"]


def test_profiler_brackets_the_window_only():
    """Starting the profiler takes hundredths of a second and stopping it
    most of a minute: it starts as the window opens and stops after the
    drain, so neither stalls a request that is measured."""
    import time

    from chipbench.harness import loop, traffic, weights
    from repro.engine import Engine, EngineConfig
    from repro.launch.mesh import make_host_mesh
    from repro.runtime import ShardingPolicy

    c = tiny.cell("tiny-open")
    a, ec = c.arch, c.config["engine"]
    engine = Engine(c.architecture.arch_config(a),
                    weights.make_params(c.architecture, a, SEED),
                    make_host_mesh(), ShardingPolicy(), EngineConfig(**ec))
    calls = []
    tr = {"start": lambda: calls.append(("start", time.perf_counter())),
          "stop": lambda: calls.append(("stop", time.perf_counter()))}
    gen = traffic.generate(c.mix, SEED, 2.0, a["vocab_size"], 5.0,
                           max_len=ec["max_len"])
    served = loop.serve(engine, gen, 2.0, 5.0, trace=tr)
    (start, t_start), (stop, t_stop) = calls
    assert (start, stop) == ("start", "stop")
    assert t_start <= served.t_open and t_stop >= served.t_close
    assert all(st.t1 <= t_stop for st in served.steps)
    assert not any(st.t0 < t_start < st.t1 for st in served.steps)
