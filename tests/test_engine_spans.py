"""The engine's host spans (``engine.*``): where they open, what they
carry, and that they change nothing the engine serves.

Most tests record the spans by swapping the scheduler's ``_span`` for a
recorder that keeps each span's name, arguments and parent; one runs the
engine under the real profiler and reads the spans back from its trace.
"""
import contextlib
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.engine import Engine, EngineConfig, Request
from repro.engine import scheduler
from repro.launch.mesh import make_host_mesh
from repro.models import init_params
from repro.runtime import ShardingPolicy

DISPATCH = {"prefill_chunk": "engine.prefill",
            "prefill_batch": "engine.prefill",
            "decode_block": "engine.decode",
            "spec_step": "engine.verify"}
SYNC_READS = {"cursor", "ready", "tokens", "produced", "first_token",
              "logits"}


@pytest.fixture(scope="module")
def mesh():
    return make_host_mesh()


@pytest.fixture(scope="module")
def cfg():
    return configs.reduced(configs.get("qwen2-7b"))


@pytest.fixture(scope="module")
def params(cfg):
    return init_params(cfg, jax.random.PRNGKey(0))


def _requests(cfg, n=5, length=12, max_new=5):
    toks = np.asarray(jax.random.randint(jax.random.PRNGKey(3),
                                         (n, length), 0, cfg.vocab_size,
                                         jnp.int32))
    # different budgets so slots free at different steps and later
    # admissions join running slots
    return [Request(rid=i, prompt=list(toks[i]), max_new=max_new + i % 3)
            for i in range(n)]


def _serve(mesh, cfg, params, **ec):
    kw = dict(max_slots=2, max_len=64, chunk_size=8, decode_block=2)
    kw.update(ec)
    with mesh:
        eng = Engine(cfg, params, mesh, ShardingPolicy(), EngineConfig(**kw))
        eng.run(_requests(cfg))
    return eng


class SpanLog:
    """Stands in for ``jax.profiler.TraceAnnotation``: records each span
    as ``{"name", "args", "parent", "children"}`` in opening order."""

    def __init__(self):
        self.spans, self._open = [], []

    @contextlib.contextmanager
    def __call__(self, name, **args):
        s = {"name": name, "args": args, "children": [],
             "parent": self._open[-1] if self._open else None}
        if s["parent"] is not None:
            s["parent"]["children"].append(s)
        self.spans.append(s)
        self._open.append(s)
        try:
            yield
        finally:
            self._open.pop()

    def named(self, name):
        return [s for s in self.spans if s["name"] == name]


def descendants(s):
    for c in s["children"]:
        yield c
        yield from descendants(c)


MODES = {"chunked": {}, "batched": {"prefill_batch": 2},
         "spec": {"spec_k": 2}}


@pytest.fixture(scope="module")
def recorded(mesh, cfg, params):
    """``recorded(mode) -> (engine, SpanLog)``: one served run per mode."""
    runs = {}

    def get(mode):
        if mode not in runs:
            log = SpanLog()
            with pytest.MonkeyPatch.context() as mp:
                mp.setattr(scheduler, "_span", log)
                runs[mode] = _serve(mesh, cfg, params, **MODES[mode]), log
        return runs[mode]
    return get


@pytest.mark.parametrize("mode", MODES)
def test_each_dispatch_has_one_span_with_its_event(recorded, mode):
    eng, log = recorded(mode)
    kinds = {ev.kind for ev in eng.trace}
    assert kinds & set(DISPATCH)
    dispatches = [s for s in log.spans if s["name"] in DISPATCH.values()]
    by_ev = {}
    for s in dispatches:
        by_ev.setdefault(s["args"]["ev"], []).append(s["name"])
    want = {i: [DISPATCH[ev.kind]] for i, ev in enumerate(eng.trace)
            if ev.kind in DISPATCH}
    assert by_ev == want


@pytest.mark.parametrize("mode", MODES)
def test_spans_nest_in_steps_and_carry_host_values(recorded, mode):
    eng, log = recorded(mode)
    steps = log.named("engine.step")
    assert [s["args"]["step"] for s in steps] == list(range(eng.step_idx))
    assert all(s["parent"] is None for s in steps)
    for s in log.spans:
        if s["name"] != "engine.step":
            top = s
            while top["parent"] is not None:
                top = top["parent"]
            assert top["name"] == "engine.step", s["name"]
        for v in s["args"].values():
            assert type(v) in (int, str), (s["name"], type(v))
    assert {s["args"]["what"] for s in log.named("engine.sync")} \
        <= SYNC_READS


def test_syncs_per_decode_step(recorded):
    """A step that dispatches a decode block waits for the device once
    per running slot (its cursor), three times for the block (ready,
    tokens, produced) and once per admission (its first token)."""
    eng, log = recorded("chunked")
    blocks = iter([ev for ev in eng.trace if ev.kind == "decode_block"])
    n = 0
    for step in log.named("engine.step"):
        inner = list(descendants(step))
        if not any(s["name"] == "engine.decode" for s in inner):
            continue
        slots = len(next(blocks).slots)
        admits = sum(s["name"] == "engine.admit" for s in inner)
        syncs = [s["args"]["what"] for s in inner
                 if s["name"] == "engine.sync"]
        assert len(syncs) == slots + 3 + admits
        assert sorted(syncs) == sorted(["cursor"] * slots
                                       + ["first_token"] * admits
                                       + ["ready", "tokens", "produced"])
        n += 1
    assert n == sum(ev.kind == "decode_block" for ev in eng.trace) > 1


def _host_spans(trace_dir):
    from jax.profiler import ProfileData
    path = sorted(pathlib.Path(trace_dir).rglob("*.xplane.pb"))[-1]
    pd = ProfileData.from_file(str(path))
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats))
            for p in pd.planes if p.name.startswith("/host:")
            for line in p.lines for e in line.events
            if e.name.startswith("engine.")]


def test_profiler_sees_nested_spans_and_tokens_do_not_change(
        mesh, cfg, params, tmp_path):
    with jax.profiler.trace(str(tmp_path)):
        traced = _serve(mesh, cfg, params)
    plain = _serve(mesh, cfg, params)
    assert ({r: res.tokens for r, res in traced.results.items()}
            == {r: res.tokens for r, res in plain.results.items()})
    spans = _host_spans(tmp_path)
    steps = [s for s in spans if s[0] == "engine.step"]
    assert len(steps) == traced.step_idx
    assert sorted(s[3]["step"] for s in steps) == list(range(len(steps)))
    for name, a, b, _ in spans:
        if name != "engine.step":
            assert any(sa <= a and b <= sb for _, sa, sb, _ in steps), name
    evs = sorted(s[3]["ev"] for s in spans
                 if s[0] in ("engine.prefill", "engine.decode"))
    assert evs == [i for i, ev in enumerate(traced.trace)
                   if ev.kind in ("prefill_chunk", "decode_block")]
