"""The unified Scenario→Report API: resolution, JSON round-trip, compare,
no-drift vs the legacy Forecaster wiring, CLI smoke, measured pipeline."""
import dataclasses
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

from repro import api
from repro.configs import PAPER_VARIANTS, get as get_arch
from repro.configs.base import Variant
from repro.core import Forecaster, WorkloadModel, hardware

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# Scenario
# ---------------------------------------------------------------------------

def test_scenario_resolution():
    scn = api.Scenario(model="llama2-7b", variant="bf16-int4-kv4")
    assert scn.arch.name == "llama2-7b"
    assert scn.variant_obj.kv_dtype == "int4"
    # object forms pass through
    scn2 = api.Scenario(model=get_arch("qwen2-7b"),
                        variant=Variant(name="custom", fused=True))
    assert scn2.arch.name == "qwen2-7b" and scn2.variant_obj.fused
    # reduced resolves the CPU-sized config
    assert api.Scenario(model="qwen2-7b", reduced=True).arch.name \
        == "qwen2-7b-reduced"


def test_scenario_past_lens_sets_batch():
    scn = api.Scenario(model="llama2-7b", past_lens=[100, 200, 300])
    assert scn.batch == 3
    assert scn.decode_past_lens == (100, 200, 300)
    # uniform default: prompt_len replicated over batch
    scn = api.Scenario(model="llama2-7b", batch=2, prompt_len=64)
    assert scn.decode_past_lens == (64, 64)


def test_scenario_gen_lens_sets_n_requests():
    scn = api.Scenario(model="llama2-7b", gen_lens=[8, 6, 4])
    assert scn.n_requests == 3
    assert scn.request_gen_lens == (8, 6, 4)


def test_scenario_validation():
    with pytest.raises(ValueError):
        api.Scenario(model="llama2-7b", prompt_len=0)
    # registry names fail fast at construction (and thus in from_dict)
    with pytest.raises(KeyError, match="unknown variant"):
        api.Scenario(model="llama2-7b", variant="nope")
    with pytest.raises(KeyError, match="unknown arch"):
        api.Scenario(model="nope")
    with pytest.raises(KeyError, match="unknown variant"):
        api.Scenario.from_dict({"model": "llama2-7b", "variant": "custom"})


def test_scenario_dict_roundtrip():
    scn = api.Scenario(model="llama2-7b", variant="bf16-int4", batch=2,
                       prompt_len=256, gen_len=32, chunk=64,
                       lora_rank=16, temperature=0.5)
    assert api.Scenario.from_dict(scn.to_dict()) == scn


# ---------------------------------------------------------------------------
# Report: JSON round-trip + compare
# ---------------------------------------------------------------------------

def _small_forecast(**kw):
    scn = api.Scenario(model="llama2-7b", variant="bf16-int4-kv4",
                       prompt_len=128, gen_len=8)
    return api.forecast(scn, kw.pop("hw", "tpu-v5e"), **kw)


def test_report_json_roundtrip():
    r = _small_forecast(em=0.8)
    r2 = api.Report.from_json(r.to_json())
    assert r2 == r
    # every leaf survives, not just the headline metrics
    assert r2.phases["prefill"].ops == r.phases["prefill"].ops
    assert r2.phases["decode"].kv_rd == r.phases["decode"].kv_rd
    assert r2.scenario == r.scenario
    d = r.to_dict()
    assert d["schema"] == api.SCHEMA_VERSION
    json.dumps(d)  # plain-JSON serializable, no custom encoder needed


def test_report_rejects_unknown_source_and_newer_schema():
    r = _small_forecast()
    with pytest.raises(ValueError, match="source"):
        dataclasses.replace(r, source="guess")
    newer = dict(r.to_dict(), schema=api.SCHEMA_VERSION + 1)
    with pytest.raises(ValueError, match="newer"):
        api.Report.from_dict(newer)


def test_compare_forecast_vs_measured_pair():
    fc = _small_forecast(em=0.8)
    measured = dataclasses.replace(
        fc, source="measured", hardware="host",
        ttft_s=fc.ttft_s * 2, tpot_s=fc.tpot_s * 4, tps=fc.tps / 4)
    d = api.compare(fc, measured)
    assert d.ttft.ratio == pytest.approx(0.5)
    assert d.tpot.ratio == pytest.approx(0.25)
    assert d.tps.ratio == pytest.approx(4.0)
    assert d.tpot.rel_err == pytest.approx(-0.75)
    assert d.forecast_hw == "tpu-v5e" and d.measured_hw == "host"
    json.dumps(d.to_dict())


def test_compare_reports_forecast_error_first_class():
    fc = _small_forecast(em=0.8)
    measured = dataclasses.replace(
        fc, source="measured", hardware="host",
        ttft_s=fc.ttft_s * 2, tpot_s=fc.tpot_s * 4, tps=fc.tps / 4)
    d = api.compare(fc, measured)
    # signed relative error per headline metric: (forecast - measured)/measured
    assert d.forecast_error["ttft"] == pytest.approx(-0.5)
    assert d.forecast_error["tpot"] == pytest.approx(-0.75)
    assert d.forecast_error["tps"] == pytest.approx(3.0)
    assert d.worst_abs_error == pytest.approx(3.0)
    dd = d.to_dict()
    assert dd["forecast_error"]["tps"] == pytest.approx(3.0)
    assert dd["worst_abs_error"] == pytest.approx(3.0)


def test_bench_forecast_error_regression_gate():
    import pathlib
    import sys
    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))
    try:
        from benchmarks.run import _forecast_error_regression
    finally:
        sys.path.pop(0)
    prev = {"git_sha": "abc", "forecast_error": {"worst_abs": 1.0}}
    ok = {"benchmark": "engine", "forecast_error": {"worst_abs": 1.1}}
    bad = {"benchmark": "engine",
           "forecast_error": {"worst_abs": 1.6, "hardware": "host-cpu"}}
    assert _forecast_error_regression(prev, ok) is None
    msg = _forecast_error_regression(prev, bad)
    assert msg and "regressed" in msg and "abc" in msg
    # noise floor: 25% relative AND 2 points absolute must both trip
    small_base = {"forecast_error": {"worst_abs": 0.01}}
    small_new = {"benchmark": "e", "forecast_error": {"worst_abs": 0.02}}
    assert _forecast_error_regression(small_base, small_new) is None
    # legacy history entries without the section never gate
    assert _forecast_error_regression({}, bad) is None
    assert _forecast_error_regression(None, bad) is None


def test_compare_rejects_different_workloads():
    a = _small_forecast()
    b = dataclasses.replace(a, source="measured", model="qwen2-7b")
    with pytest.raises(ValueError, match="different workloads"):
        api.compare(a, b)


# ---------------------------------------------------------------------------
# no drift: api.forecast ≡ legacy Forecaster wiring (bit-for-bit)
# ---------------------------------------------------------------------------

def _assert_matches_legacy(batch, past, em, variant):
    """Uniform ``past_lens`` must reproduce the legacy
    ``Forecaster.tpot(wm.decode_step(...))`` path with zero drift — the
    redesign may not change a single bit of the paper-table numbers."""
    scn = api.Scenario(model="llama2-7b", variant=variant,
                       past_lens=(past,) * batch, prompt_len=past, gen_len=1)
    r = api.forecast(scn, "nvidia-v100", em=em)
    wm = WorkloadModel(get_arch("llama2-7b"), PAPER_VARIANTS[variant])
    fc = Forecaster(hardware.get("nvidia-v100"))
    assert r.tpot_s == fc.tpot(wm.decode_step(batch, past), em=em)
    assert r.ttft_s == fc.ttft(wm.prefill(batch, past), em=em).latency
    assert r.tps == batch / r.tpot_s


@pytest.mark.parametrize("batch,past,em,variant", [
    (1, 2048, 0.50, "bf16-bf16"),     # Table 10 V100 row
    (1, 512, 0.10, "fp16-fp16"),
    (4, 333, 0.80, "bf16-int4-kv4"),
    (2, 1, 1.00, "bf16-int4-fused"),
])
def test_forecast_uniform_matches_legacy_tpot_bitforbit(batch, past, em,
                                                        variant):
    _assert_matches_legacy(batch, past, em, variant)


try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:  # optional dev dependency
    HAVE_HYPOTHESIS = False


if HAVE_HYPOTHESIS:
    @settings(max_examples=25, deadline=None)
    @given(batch=st.integers(1, 4), past=st.integers(1, 4096),
           em=st.floats(0.05, 1.0),
           variant=st.sampled_from(sorted(PAPER_VARIANTS)))
    def test_forecast_matches_legacy_property(batch, past, em, variant):
        _assert_matches_legacy(batch, past, em, variant)


def test_forecast_mixed_past_lens_between_uniform_bounds():
    lo = api.forecast(api.Scenario(model="llama2-7b", past_lens=(10, 10)),
                      "v5e").tpot_s
    hi = api.forecast(api.Scenario(model="llama2-7b", past_lens=(500, 500)),
                      "v5e").tpot_s
    mid = api.forecast(api.Scenario(model="llama2-7b", past_lens=(10, 500)),
                       "v5e").tpot_s
    assert lo < mid < hi


def test_forecast_chunked_prefill_adds_kv_reread():
    plain = api.forecast(api.Scenario(model="llama2-7b", prompt_len=256,
                                      gen_len=1), "v5e")
    chunked = api.forecast(api.Scenario(model="llama2-7b", prompt_len=256,
                                        gen_len=1, chunk=64), "v5e")
    assert chunked.phases["prefill"].kv_rd > plain.phases["prefill"].kv_rd
    assert chunked.ttft_s > 0


def test_forecast_lora_scenario_reports_merge_time():
    r = api.forecast(api.Scenario(model="llama2-7b", variant="bf16-int4",
                                  lora_rank=64, prompt_len=64, gen_len=1),
                     "v5e")
    assert r.extras["lora_update_s"] > 0
    assert "lora_update" in r.phases


# ---------------------------------------------------------------------------
# hardware registry satellites
# ---------------------------------------------------------------------------

def test_hardware_list_and_aliases():
    names = hardware.list()
    assert "tpu-v5e" in names and names == sorted(names)
    assert hardware.get("v100") is hardware.NVIDIA_V100
    assert hardware.get("V100") is hardware.NVIDIA_V100
    assert hardware.get("Tpu-V5e") is hardware.TPU_V5E
    assert hardware.get("cpu") is hardware.RYZEN_9_HX370_CPU
    # spec passthrough
    assert hardware.get(hardware.TPU_V5E) is hardware.TPU_V5E
    with pytest.raises(KeyError, match="known:.*nvidia-v100"):
        hardware.get("h100")


# ---------------------------------------------------------------------------
# sweep
# ---------------------------------------------------------------------------

def test_sweep_names_and_grid():
    scn = api.Scenario(model="llama2-7b", prompt_len=64, gen_len=4)
    rs = api.sweep(scn, ["cpu", "v100"], tops=[10, 100], bw=[100, 800])
    assert [r.hardware for r in rs[:2]] == ["ryzen-9-hx370-cpu",
                                            "nvidia-v100"]
    assert len(rs) == 2 + 4
    # memory-bound decode: TPS depends on BW, not TOPS
    by_name = {r.hardware: r for r in rs}
    assert by_name["grid-10tops-800gbps"].tps == pytest.approx(
        by_name["grid-100tops-800gbps"].tps)
    with pytest.raises(ValueError, match="together"):
        api.sweep(scn, tops=[10])
    with pytest.raises(ValueError, match="needs hardware"):
        api.sweep(scn)


# ---------------------------------------------------------------------------
# measured pipeline (tiny reduced engine run) + trace replay + compare
# ---------------------------------------------------------------------------

def test_measure_and_trace_replay_compare():
    scn = api.Scenario(model="qwen2-7b", reduced=True, batch=2,
                       n_requests=3, prompt_len=16, gen_len=4, chunk=8,
                       decode_block=2)
    measured = api.measure(scn)
    assert measured.source == "measured"
    assert measured.hardware == "host"
    assert measured.tps > 0 and measured.ttft_s > 0
    assert measured.extras["mode"] == "engine"
    assert measured.extras["tokens"] == 3 * 4
    assert measured.trace  # replayable attachment
    # same schema both sides: every forecast field exists on the measured one
    fc = api.forecast(scn, "cpu", em=0.8, trace=measured.trace)
    assert set(fc.to_dict()) == set(measured.to_dict())
    assert fc.phases["prefill"] == measured.phases["prefill"]
    d = api.compare(fc, measured)
    assert d.tps.ratio > 0
    # trace replay must match the twin's aggregate TPS exactly
    from repro.engine import ForecastTwin
    twin = ForecastTwin(scn.arch, hardware.get("cpu"), scn.variant_obj,
                        em=0.8, prefill_ec=1.0, prefill_em=0.8)
    assert fc.tps == twin.replay(measured.trace).tps


# ---------------------------------------------------------------------------
# shared-prefix traffic: analytical knobs + measured/forecast agreement
# ---------------------------------------------------------------------------

def test_scenario_shared_prefix_roundtrip_and_validation():
    scn = api.Scenario(model="llama2-7b", prompt_len=64,
                       shared_prefix_len=48, block_size=16,
                       prefix_cache=False)
    back = api.Scenario.from_dict(scn.to_dict())
    assert back == scn
    assert back.shared_prefix_len == 48 and back.block_size == 16
    assert not back.prefix_cache
    assert back.cached_prefix_len == 0        # cache disabled: no hit
    with pytest.raises(ValueError, match="shared_prefix_len"):
        api.Scenario(model="llama2-7b", prompt_len=64, shared_prefix_len=65)
    with pytest.raises(ValueError, match="block_size"):
        api.Scenario(model="llama2-7b", block_size=0)


def test_scenario_cached_prefix_block_alignment():
    # hits are full blocks only, capped at prompt_len - 1
    scn = api.Scenario(model="llama2-7b", prompt_len=64,
                       shared_prefix_len=40, block_size=16)
    assert scn.cached_prefix_len == 32        # 40 aligned down to 2 blocks
    full = api.Scenario(model="llama2-7b", prompt_len=64,
                        shared_prefix_len=64, block_size=16)
    assert full.cached_prefix_len == 63       # one token must compute logits


def test_forecast_shared_prefix_ttft_between_warm_and_cold():
    base = api.Scenario(model="llama2-7b", batch=4, prompt_len=512,
                        gen_len=64, chunk=128)
    shared = dataclasses.replace(base, shared_prefix_len=384, block_size=16)
    r = api.forecast(shared, "tpu-v5e", em=0.8)
    x = r.extras
    assert x["ttft_warm_s"] < r.ttft_s < x["ttft_cold_s"]
    assert x["ttft_savings_s"] == pytest.approx(
        x["ttft_cold_s"] - x["ttft_warm_s"])
    assert x["cached_tokens"] == 384
    assert x["prefix_hit_rate"] == pytest.approx(384 * 3 / (512 * 4))
    assert "prefill_warm" in r.phases
    assert r.phases["prefill_warm"].ops < r.phases["prefill"].ops
    # the no-prefix scenario is untouched by the new knobs (legacy path)
    plain = api.forecast(base, "tpu-v5e", em=0.8)
    assert "ttft_warm_s" not in plain.extras
    assert "prefill_warm" not in plain.phases


def test_measure_shared_prefix_hit_rate_agrees_with_forecast():
    """Measured radix-cache hit rate vs the analytical forecast of the
    same traffic: identical, because both share full blocks only."""
    scn = api.Scenario(model="qwen2-7b", reduced=True, batch=2,
                       n_requests=3, prompt_len=24, gen_len=4, chunk=8,
                       shared_prefix_len=16, block_size=8, decode_block=2)
    measured = api.measure(scn)
    assert measured.extras["prefix_hit_tokens"] == 16 * 2   # 2 warm reqs
    fc = api.forecast(scn, "cpu", em=0.8)
    assert measured.extras["prefix_hit_rate"] == pytest.approx(
        fc.extras["prefix_hit_rate"])
    # replaying the measured trace reports the same hit rate + a savings
    replay = api.forecast(scn, "cpu", em=0.8, trace=measured.trace)
    assert replay.extras["trace_prefix_hit_rate"] == pytest.approx(
        measured.extras["prefix_hit_rate"])
    assert replay.extras["trace_ttft_savings_s"] > 0
    assert replay.extras["trace_prefill_savings_s"] > 0


# ---------------------------------------------------------------------------
# CLI smoke
# ---------------------------------------------------------------------------

def _run_cli(*argv):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(ROOT, "src") + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    return subprocess.run([sys.executable, "-m", "repro", *argv],
                          capture_output=True, text=True, timeout=300,
                          env=env, cwd=ROOT)


def test_cli_forecast_json_parses():
    r = _run_cli("forecast", "--model", "llama2-7b", "--variant",
                 "bf16-int4-kv4", "--hw", "tpu-v5e", "--prompt", "2048",
                 "--gen", "256", "--json")
    assert r.returncode == 0, r.stderr[-2000:]
    d = json.loads(r.stdout)
    assert d["source"] == "forecast" and d["hardware"] == "tpu-v5e"
    assert d["tps"] > 0 and "prefill" in d["phases"]
    # the JSON is a full Report round-trip
    rep = api.Report.from_dict(d)
    assert rep.model == "llama2-7b"


def test_cli_compare_roundtrip(tmp_path):
    fc = _small_forecast(em=0.8)
    measured = dataclasses.replace(fc, source="measured", hardware="host",
                                   tps=fc.tps / 2)
    (tmp_path / "fc.json").write_text(fc.to_json())
    (tmp_path / "ms.json").write_text(measured.to_json())
    r = _run_cli("compare", str(tmp_path / "fc.json"),
                 str(tmp_path / "ms.json"), "--json")
    assert r.returncode == 0, r.stderr[-2000:]
    assert json.loads(r.stdout)["tps"]["ratio"] == pytest.approx(2.0)


def test_cli_unknown_model_exits_nonzero():
    r = _run_cli("forecast", "--model", "nope", "--hw", "v5e")
    assert r.returncode == 2
    assert "unknown arch" in r.stderr


def test_benchmarks_run_rejects_unknown_module():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    r = subprocess.run([sys.executable, "-m", "benchmarks.run", "nope"],
                       capture_output=True, text=True, timeout=120,
                       env=env, cwd=ROOT)
    assert r.returncode == 2
    assert "unknown benchmark module" in r.stderr


_CACHE_PROBE = """
import jax, jax.numpy as jnp
from jax import monitoring
from repro.launch.compile_cache import enable_compile_cache
hits = []
monitoring.register_event_listener(
    lambda ev, **_: hits.append(ev)
    if ev == "/jax/compilation_cache/cache_hits" else None)
print(enable_compile_cache())
print(jax.config.jax_compilation_cache_dir)
jax.jit(lambda x: jnp.sin(x) @ x.T)(jnp.ones((64, 64))).block_until_ready()
print(len(hits))
"""


def _cache_probe(env_dir):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    env["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    r = subprocess.run([sys.executable, "-c", _CACHE_PROBE],
                       capture_output=True, text=True, timeout=120,
                       env=env, cwd=ROOT)
    assert r.returncode == 0, r.stderr[-2000:]
    return r.stdout.split()


def test_compile_cache_honours_env_dir_and_hits_on_rerun(tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set the helper sets no other
    directory, the cache lands there, and a second identical process
    loads from it."""
    first = _cache_probe(tmp_path)
    assert first[:2] == [str(tmp_path), str(tmp_path)]
    assert any(tmp_path.iterdir())
    assert int(first[2]) == 0
    assert int(_cache_probe(tmp_path)[2]) >= 1


def test_compile_cache_defaults_to_a_fixed_dir_in_the_checkout():
    from repro.launch import compile_cache
    assert compile_cache.CACHE_DIR == Path(ROOT) / ".jax_cache"
    ignored = (Path(ROOT) / ".gitignore").read_text().split()
    assert ".jax_cache/" in ignored
