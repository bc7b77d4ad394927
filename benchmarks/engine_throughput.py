"""Engine throughput: measured continuous-batching TPS vs the LIFE twin,
via the Scenario→Report API — for BOTH attention read paths.

Runs the serving engine on CPU (reduced model) across several
batch/traffic settings (``api.measure``), then replays each run's own
scheduler trace through the analytical twin
(``api.forecast(..., trace=measured.trace)``).  Per setting:

* ``forecast_tps_cpu``  — twin of the REDUCED model (the one actually
  measured, priced for the attention impl actually run) on the paper's
  Ryzen CPU spec: the apples-to-apples comparison, diffed against the
  measured report with ``api.compare``;
* ``forecast_tps_v5e_gather`` / ``forecast_tps_v5e_paged`` — twins of the
  FULL model on the TPU v5e target, one per attention impl: the gather
  path pays the per-layer page rematerialization of its block-table
  gather, the paged path prices the Pallas paged flash kernels (fused
  attention core, no page buffer).  Their ratio is the forecast speedup
  of shipping the kernel — the gather-vs-paged delta as a forecastable
  quantity.

The point (paper Fig. 2 loop, extended to multi-request traffic): the
same trace drives measured and forecast sides, so scheduling effects
(admission order, slot reuse, mixed KV lengths, radix prefix-cache hits)
are identical.  The ``shared-prefix`` setting exercises the block-paged
cache's prefix reuse; the ``paged-*`` setting measures the Pallas kernels
themselves — in interpret mode on this CPU container, where skipping
past-cursor KV blocks still beats rematerializing the gather path's full
padded virtual width (~2× TPS at the same geometry, a CPU timing; the
kernels' speed on the TPU is not measured here).  The twin
costs the schedule's useful work (active slots, valid
chunk tokens); the measured engine also pays for static-shape padding —
see the scope note in ``repro.engine.forecast_twin``.

``benchmarks.run`` turns these rows into the ``BENCH_engine.json``
artifact (measured TPS, forecast TPS, delta, both-impl deployment
forecasts per setting) via :func:`bench_artifact`, tracking the perf
trajectory across PRs.

The ``spec-*`` row measures speculative decoding (k drafts per slot per
step, one batched multi-query verify) against its own non-speculative
baseline on a deterministic high-acceptance motif workload, then replays
the speculative trace — measured per-step acceptance and all — through
the twin for the trace-grounded v5e speedup and the break-even
acceptance α* at which speculation starts paying on that target.

Tensor-parallel settings (``tp-*``) run the SAME engine sharded over KV
heads on a ``model=tp`` host-device mesh (this module requests 8 XLA host
devices before JAX initializes; settings whose mesh exceeds the devices
actually visible are skipped) and forecast the per-chip schedule with the
plan's collective traffic priced in — measured-vs-forecast TPS per tp
degree.  The tp runs use a reduced config with ``n_kv_heads=4`` so tp=4
divides the head counts.  Pipeline-parallel settings (``pp-*`` /
``tp*xpp*``) split the engine's layer scan into stages over a ``pipe``
mesh axis — the tp×pp sweep of the forecast stack, with the twin
replaying each sharded run's own trace.

Every setting also carries ``forecast_tps_host`` / ``forecast_error_host``:
the same trace replayed on the CALIBRATED spec of the machine underfoot
(``benchmarks.calibrate_host`` registers ``host-cpu`` from GEMM /
bandwidth / dispatch micro-benchmarks).  Unlike the Ryzen-datasheet twin,
this one is an accuracy target: its signed relative TPS error is the
fair measured-vs-forecast residual on the host.

    PYTHONPATH=src python -m benchmarks.engine_throughput
"""
import dataclasses

from repro.launch.mesh import ensure_host_device_count

ensure_host_device_count(8)    # before any JAX device use; flags preserved

from repro import api, configs
from repro.configs.base import Variant

ARCH = "qwen2-7b"
PROMPT, NEW = 32, 16

#: (label, n_requests, max_slots, decode_block, shared_prefix_len,
#:  attn_impl, tp, pp)
SETTINGS = [
    ("serial-1slot", 4, 1, 8, None, "gather", 1, 1),
    ("batch-2slot", 4, 2, 8, None, "gather", 1, 1),
    ("batch-4slot", 8, 4, 8, None, "gather", 1, 1),
    ("overload-2slot-8req", 8, 2, 4, None, "gather", 1, 1),
    ("shared-prefix-16of32", 6, 2, 8, 16, "gather", 1, 1),
    ("paged-2slot", 4, 2, 8, None, "paged", 1, 1),
    # sharded engine: same model, same traffic at tp∈{1,4} — the tp1 row
    # is the apples-to-apples baseline for the sharding delta, so BOTH
    # rows use the 4-head override (the stock reduced config's
    # n_kv_heads=2 cannot shard 4 ways)
    ("tp1-2slot", 4, 2, 8, None, "gather", 1, 1),
    ("tp4-2slot", 4, 2, 8, None, "gather", 4, 1),
    # tp×pp sweep: the stock reduced config's 2 layers split into 2
    # stages (pipe axis); tp2xpp2 composes the KV-head and layer-stage
    # shardings on a 2×2 model×pipe mesh
    ("pp2-2slot", 4, 2, 8, None, "gather", 1, 2),
    ("tp2xpp2-2slot", 4, 2, 8, None, "gather", 2, 2),
]

#: labels of the tp-comparison rows (shared 4-head reduced config)
_TP_ROWS = ("tp1-2slot", "tp4-2slot")

#: speculative decoding: identical motif prompts (a shared prefix covering
#: the whole prompt, itself an 8-token repeated motif) are a deterministic
#: high-acceptance workload — agent-loop/templated traffic the n-gram
#: drafter locks onto.  The seed is chosen so the reduced model's T=0
#: output continues the motif cycle (acceptance ≈ 1); the twin replays the
#: MEASURED acceptance, so the forecast side stays honest at any seed.
SPEC_K = 4
SPEC_SEED = 2


def _spec_scenario(spec_k: int) -> api.Scenario:
    return api.Scenario(
        model=ARCH, variant=Variant(name="bf16-fused", fused=True),
        reduced=True, batch=4, prompt_len=40, gen_len=48, n_requests=4,
        chunk=16, decode_block=8, block_size=8, shared_prefix_len=40,
        prompt_motif_len=8, attn_impl="gather", seed=SPEC_SEED,
        spec_k=spec_k)


def _spec_row():
    """Measured spec-vs-plain speedup + the forecastable quantities."""
    m0 = api.measure(_spec_scenario(0))
    m4 = api.measure(_spec_scenario(SPEC_K))
    full = dataclasses.replace(_spec_scenario(SPEC_K), model=ARCH,
                               reduced=False)
    v5e = api.forecast(full, "tpu-v5e", em=0.8, trace=m4.trace)
    breakeven = v5e.extras["spec_breakeven_acceptance"]
    derived = {
        "requests": 4, "slots": 4, "attn_impl": "gather", "tp": 1,
        "spec_k": SPEC_K,
        "measured_tps_plain": round(m0.tps, 1),
        "measured_tps_spec": round(m4.tps, 1),
        "measured_spec_speedup": round(m4.tps / m0.tps, 3),
        "measured_spec_acceptance": round(
            m4.extras["spec_acceptance"], 3),
        "measured_spec_tokens_per_step": round(
            m4.extras["spec_tokens_per_step"], 3),
        # twin replay of the measured trace (measured per-step acceptance)
        # vs the same trace despeculated — the trace-grounded speedup
        "forecast_spec_speedup_trace_v5e": round(
            v5e.extras["trace_spec_speedup"], 3),
        "forecast_breakeven_acceptance_v5e": (
            round(breakeven, 4) if breakeven is not None else None),
        "forecast_tps_v5e_spec": round(v5e.tps, 1),
    }
    return f"engine/spec-k{SPEC_K}-motif8", derived


#: Multi-tenant LoRA row: a 64-tenant mixed-rank population (Zipf-skewed
#: popularity) served three ways at the same geometry — the grouped
#: batched path (Pallas grouped low-rank matmul / gather reference), a
#: naive per-tenant loop (1 slot: one adapter resident and applied at a
#: time, the strawman every batch-unaware LoRA server runs), and the
#: merged-weights ceiling (single adapter folded into W, cost-identical
#: to the base model).  The grouped path must hold >= 2x the naive loop
#: and land within 1.3x of the merged ceiling — both asserted, so a
#: regression fails the benchmark run itself, not just the history gate.
LORA_TENANTS = 64
LORA_RANKS = (4, 8, 16)
LORA_POP = 0.8


def _lora_cfg():
    """Mid-size reduced arch for the LoRA ratio gates.  At the stock
    128-d reduced config a rank-16 adapter pool is ~25% of the
    projection FLOPs, so the merged-ceiling ratio floor sits on top of
    the 1.3x gate by construction; at d_model=512 the adapter share has
    realistic proportions and the gate measures serving overhead, not
    toy-geometry arithmetic."""
    return configs.reduced(configs.get(ARCH), d_model=512, n_heads=8,
                           head_dim=64, n_kv_heads=2, d_ff=1024)


def _lora_scenario(**over) -> api.Scenario:
    # decode-dominated (gen 2x the other rows): steady-state serving TPS,
    # not admission-time adapter loads, is the quantity under test
    kw = dict(model=_lora_cfg(), reduced=False,
              variant=Variant(name="bf16-fused", fused=True),
              batch=4, prompt_len=PROMPT, gen_len=2 * NEW,
              n_requests=8, chunk=16, decode_block=8, seed=5,
              lora_n_tenants=LORA_TENANTS, lora_ranks=LORA_RANKS,
              lora_popularity=LORA_POP)
    kw.update(over)
    return api.Scenario(**kw)


def _best(scn, n=3):
    """Best-of-n measured report: the steady-state TPS estimate the
    ratio gates are judged on (single ~0.2 s walls on a shared CPU
    container are too noisy to gate a 1.3x ratio)."""
    return max((api.measure(scn) for _ in range(n)), key=lambda r: r.tps)


def _lora_row():
    """Measured grouped-vs-naive-vs-merged TPS + the forecast quantities."""
    scn = _lora_scenario()
    multi = _best(scn)
    naive = _best(_lora_scenario(batch=1))
    merged = _best(_lora_scenario(
        lora_n_tenants=0, lora_ranks=(), lora_popularity=0.0))
    vs_naive = multi.tps / naive.tps
    vs_merged = merged.tps / multi.tps
    assert vs_naive >= 2.0, \
        f"grouped multi-tenant LoRA only {vs_naive:.2f}x the naive " \
        f"per-tenant loop (must be >= 2x)"
    assert vs_merged <= 1.3, \
        f"grouped multi-tenant LoRA {vs_merged:.2f}x slower than the " \
        f"merged-adapter ceiling (must be within 1.3x)"
    host = api.forecast(scn, "host-cpu", trace=multi.trace)
    host_err = api.compare(host, multi).forecast_error["tps"]
    full = dataclasses.replace(scn, model=ARCH, reduced=False)
    v5e = api.forecast(full, "tpu-v5e", em=0.8, trace=multi.trace)
    derived = {
        "requests": scn.n_requests, "slots": scn.batch, "tp": 1,
        "tenants": LORA_TENANTS, "ranks": list(LORA_RANKS),
        "popularity": LORA_POP,
        "measured_tps_multi": round(multi.tps, 1),
        "measured_tps_naive_loop": round(naive.tps, 1),
        "measured_tps_merged": round(merged.tps, 1),
        "measured_vs_naive_speedup": round(vs_naive, 3),
        "measured_vs_merged_ratio": round(vs_merged, 3),
        "adapter_hit_rate": round(multi.extras["lora"]["hit_rate"], 3),
        "adapter_evictions": multi.extras["lora"]["evictions"],
        "forecast_tps_host": round(host.tps, 1),
        "forecast_error_host": round(host_err, 3),
        "forecast_tps_v5e": round(v5e.tps, 1),
        "forecast_lora_step_frac_v5e": round(
            v5e.extras["lora"]["step_frac"], 4),
    }
    return f"engine/lora-{LORA_TENANTS}tenants-mixed", derived


#: Poisson traffic row: offered rate + the SLO pair goodput is judged on.
#: The measured side serves the open-loop stream on the host (wall-clock
#: SLO, loose enough for a CPU container); the forecast side simulates
#: the SAME seeded trace analytically on the paper's Ryzen spec, and the
#: full model's v5e capacity (max QPS within SLO) rides along.
TRAFFIC_QPS = 20.0
TRAFFIC_SLO = (0.5, 0.05)          # (ttft_slo, tpot_slo) seconds


def _traffic_scenario() -> api.Scenario:
    return api.Scenario(
        model=ARCH, variant=Variant(name="bf16-fused", fused=True),
        reduced=True, batch=2, prompt_len=24, gen_len=8, n_requests=8,
        chunk=8, decode_block=4, prefill_batch=2, seed=3,
    ).traffic("poisson", qps=TRAFFIC_QPS,
              ttft_slo=TRAFFIC_SLO[0], tpot_slo=TRAFFIC_SLO[1])


def _traffic_row():
    """Measured vs forecast SLO goodput of one Poisson stream."""
    scn = _traffic_scenario()
    measured = api.measure(scn)
    mt = measured.extras["traffic"]
    cpu = api.forecast(scn, "cpu", em=0.8)
    ft = cpu.extras["traffic"]
    full = dataclasses.replace(scn, model=ARCH, reduced=False)
    max_qps_v5e = api.max_qps(full, "tpu-v5e", em=0.8,
                              goodput_target=0.9, qps_hi=256.0)
    derived = {
        "requests": scn.n_requests, "slots": scn.batch, "tp": 1,
        "arrival": "poisson", "qps": TRAFFIC_QPS,
        "prefill_batch": scn.prefill_batch,
        "ttft_slo_s": TRAFFIC_SLO[0], "tpot_slo_s": TRAFFIC_SLO[1],
        "measured_goodput": round(mt["goodput"], 3),
        "measured_good_qps": round(mt["good_qps"], 2),
        "measured_p99_ttft_queued_ms": round(
            mt["ttft_queued"]["p99"] * 1e3, 2),
        "measured_queue_depth_max": mt["queue_depth_max"],
        "forecast_goodput_cpu": round(ft["goodput"], 3),
        "forecast_p99_ttft_queued_ms_cpu": round(
            ft["ttft_queued"]["p99"] * 1e3, 3),
        "forecast_max_qps_v5e": round(max_qps_v5e, 2),
    }
    return f"engine/traffic-poisson-q{TRAFFIC_QPS:g}", derived


def _model_for(label: str):
    """The measured arch: the tp rows need head counts tp=4 divides."""
    if label not in _TP_ROWS:
        return ARCH, True
    cfg = configs.reduced(configs.get(ARCH), n_heads=4, n_kv_heads=4)
    return cfg, False


def rows():
    import jax

    from benchmarks.calibrate_host import register_host_spec
    register_host_spec()           # one calibration pass per process
    out = []
    for label, n_req, slots, block, shared, impl, tp, pp in SETTINGS:
        if tp * pp > jax.device_count():
            print(f"# engine/{label}: SKIPPED (tp={tp}×pp={pp} > "
                  f"{jax.device_count()} visible devices)")
            continue
        model, reduced = _model_for(label)
        # mixed budgets so completions (and slot frees) happen mid-flight
        scn = api.Scenario(
            model=model, variant=Variant(name="bf16-fused", fused=True),
            reduced=reduced, batch=slots, prompt_len=PROMPT, gen_len=NEW,
            gen_lens=tuple(NEW - 3 * (i % 3) for i in range(n_req)),
            chunk=16, decode_block=block, shared_prefix_len=shared,
            block_size=8 if shared else None, attn_impl=impl, tp=tp, pp=pp)
        measured = api.measure(scn)
        cpu = api.forecast(scn, "cpu", em=0.8, trace=measured.trace)
        host = api.forecast(scn, "host-cpu", trace=measured.trace)
        full = dataclasses.replace(scn, model=ARCH, reduced=False)
        v5e = {i: api.forecast(dataclasses.replace(full, attn_impl=i),
                               "tpu-v5e", em=0.8, trace=measured.trace)
               for i in ("gather", "paged")}
        delta = api.compare(cpu, measured)
        host_delta = api.compare(host, measured)
        derived = {
            "requests": n_req, "slots": slots, "attn_impl": impl, "tp": tp,
            "pp": pp,
            "tokens": measured.extras["tokens"],
            "wall_s": round(measured.extras["wall_s"], 2),
            "measured_tps_host": round(measured.tps, 1),
            "measured_ttft_ms_host": round(measured.ttft_s * 1e3, 2),
            "forecast_tps_cpu": round(cpu.tps, 1),
            "cpu_twin_tps_ratio": round(delta.tps.ratio, 2),
            # calibrated-host twin: same trace on the machine underfoot
            "forecast_tps_host": round(host.tps, 1),
            "forecast_error_host": round(
                host_delta.forecast_error["tps"], 3),
            "forecast_tps_v5e_gather": round(v5e["gather"].tps, 1),
            "forecast_tps_v5e_paged": round(v5e["paged"].tps, 1),
            # the kernel's forecast win over the gather path on the target
            "forecast_paged_speedup_v5e": round(
                v5e["paged"].tps / v5e["gather"].tps, 3),
            "forecast_ttft_ms_v5e": round(v5e[impl].ttft_s * 1e3, 2),
            "forecast_tpot_ms_v5e": round(v5e[impl].tpot_s * 1e3, 3),
        }
        if shared:
            derived.update(
                measured_hit_rate=round(
                    measured.extras["prefix_hit_rate"], 3),
                forecast_hit_rate=round(
                    v5e[impl].extras["trace_prefix_hit_rate"], 3),
                forecast_ttft_savings_ms_v5e=round(
                    v5e[impl].extras["trace_ttft_savings_s"] * 1e3, 3))
        out.append((f"engine/{label}", derived))
    out.append(_spec_row())
    out.append(_traffic_row())
    out.append(_lora_row())
    return out


def bench_artifact(rows_out):
    """BENCH_engine.json payload: the cross-PR perf trajectory."""
    settings = {}
    spec = {}
    traffic = {}
    lora = {}
    for name, d in rows_out:
        if "measured_vs_naive_speedup" in d:
            lora = {
                "tenants": d["tenants"],
                "ranks": d["ranks"],
                "popularity": d["popularity"],
                "measured_tps_multi": d["measured_tps_multi"],
                "measured_tps_naive_loop": d["measured_tps_naive_loop"],
                "measured_tps_merged": d["measured_tps_merged"],
                "measured_vs_naive_speedup": d["measured_vs_naive_speedup"],
                "measured_vs_merged_ratio": d["measured_vs_merged_ratio"],
                "adapter_hit_rate": d["adapter_hit_rate"],
                "forecast_tps_host": d["forecast_tps_host"],
                "forecast_error_host": d["forecast_error_host"],
                "forecast_tps_v5e": d["forecast_tps_v5e"],
            }
            continue
        if "measured_goodput" in d:
            traffic = {
                "arrival": d["arrival"],
                "qps": d["qps"],
                "ttft_slo_s": d["ttft_slo_s"],
                "tpot_slo_s": d["tpot_slo_s"],
                "measured_goodput": d["measured_goodput"],
                "measured_good_qps": d["measured_good_qps"],
                "measured_p99_ttft_queued_ms":
                    d["measured_p99_ttft_queued_ms"],
                "forecast_goodput_cpu": d["forecast_goodput_cpu"],
                "forecast_max_qps_v5e": d["forecast_max_qps_v5e"],
            }
            continue
        if "measured_spec_speedup" in d:
            spec = {
                "spec_k": d["spec_k"],
                "measured_tps_plain": d["measured_tps_plain"],
                "measured_tps_spec": d["measured_tps_spec"],
                "measured_spec_speedup": d["measured_spec_speedup"],
                "measured_spec_acceptance": d["measured_spec_acceptance"],
                "forecast_spec_speedup_trace_v5e":
                    d["forecast_spec_speedup_trace_v5e"],
                "forecast_breakeven_acceptance_v5e":
                    d["forecast_breakeven_acceptance_v5e"],
            }
            continue
        settings[name.split("/", 1)[1]] = {
            "attn_impl": d["attn_impl"],
            "tp": d["tp"],
            "pp": d["pp"],
            "measured_tps": d["measured_tps_host"],
            "forecast_tps": d["forecast_tps_cpu"],
            "tps_delta_ratio": d["cpu_twin_tps_ratio"],
            "forecast_tps_host": d["forecast_tps_host"],
            "forecast_error_host": d["forecast_error_host"],
            "mean_ttft_ms": d["measured_ttft_ms_host"],
            "forecast_tps_v5e_gather": d["forecast_tps_v5e_gather"],
            "forecast_tps_v5e_paged": d["forecast_tps_v5e_paged"],
            "forecast_paged_speedup_v5e": d["forecast_paged_speedup_v5e"],
        }
    errs = {name: s["forecast_error_host"] for name, s in settings.items()
            if s.get("forecast_error_host") is not None}
    if lora.get("forecast_error_host") is not None:
        errs[f"lora-{lora['tenants']}tenants-mixed"] = \
            lora["forecast_error_host"]
    return {
        "benchmark": "engine_throughput",
        "arch": ARCH,
        "prompt_len": PROMPT,
        "gen_len": NEW,
        "tp_degrees": sorted({d["tp"] for _, d in rows_out}),
        "pp_degrees": sorted({d.get("pp", 1) for _, d in rows_out}),
        "settings": settings,
        "spec": spec,
        "traffic": traffic,
        "lora": lora,
        # first-class forecast-accuracy summary for the calibrated host
        # spec: signed per-setting TPS error plus the scalar the CI
        # regression gate tracks across BENCH_history entries
        "forecast_error": {
            "hardware": "host-cpu",
            "metric": "tps",
            "per_setting": errs,
            "worst_abs": (round(max(abs(e) for e in errs.values()), 3)
                          if errs else None),
        },
    }


if __name__ == "__main__":
    import json
    for name, derived in rows():
        print(f"{name}: {json.dumps(derived)}")
