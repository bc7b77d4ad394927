#!/usr/bin/env python3
"""Run one benchmark cell once on the chip.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

The cell (``BENCHMARK.json`` ``workloads``) names a configuration
(``chipbench/configs/<config>.json``: model sizes, engine settings, the
check's limit) and a traffic mix (``chipbench/traffic/<mix>.json``).
The run makes the weights on the device from the seed, builds the
serving engine, warms every shape the cell uses, then drives the engine
with the mix for ``--seconds`` (``harness.loop``), checks what it served
against the plain reference (``harness.check``) and prints, as the last
line of standard output, one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the end-to-end metrics, or with ``--trace 1``
the per-layer ones), ``device``, with ``--trace 1`` ``breakdown``, and
last ``check``: each number compared beside its limit.  Earlier lines
(``[tag] {json}``) give the set-up, programs compiled inside the window
(there should be none), how late the traffic generator ran, the
analytical twin's forecast of the run's own trace, and with ``--trace 1``
the device's idle between programs by the engine's own innermost span
(``[engine_idle]``, ``harness.engine_spans``).

The configuration's ``reference`` key names its architecture
(``chipbench/arch``), which gives the program's config, the weights'
layout and the counts the readers price.

Without a TPU, with fewer chips than the cell asks for, or on a chip
missing from ``harness/peaks.py``, it exits non-zero and prints no
result.  JAX's compilation cache is ``.jax_cache/`` at the root of the
checkout.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

from chipbench.harness import spec  # noqa: E402

CACHE_DIR = ROOT / ".jax_cache"
DRAIN_CAP_S = 60.0          # a window request with no first token by
                            # then has failed
TWIN_EM = 0.8               # memory efficiency, as launch/serve.py uses


def _emit(tag: str, facts: dict) -> None:
    print(f"[{tag}] {json.dumps(facts, sort_keys=True, default=float)}",
          flush=True)


def enable_cache() -> None:
    import jax
    CACHE_DIR.mkdir(parents=True, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def warm_slots(engine, ec: dict) -> None:
    """Fill every slot once (two prefill chunks, two decode blocks each)
    so that each slot's host-side updates have run before the window."""
    from repro.engine import Request
    C = ec["chunk_size"]
    engine.run([Request(rid=-(10 + i),
                        prompt=list(range(1 + 1000 * i, 2 + 1000 * i + C)),
                        max_new=ec["decode_block"] + 2)
                for i in range(ec["max_slots"])])
    if engine.index is not None:
        engine.index.evict(engine.pool.n_blocks)
    engine.reset_metrics()


def forecast(cfg, events, recs) -> dict:
    """The tpu-v5e twin's forecast of this run's own trace, beside what
    the engine measured (both queue-exclusive TTFT)."""
    from repro.configs.base import Variant
    from repro.core import hardware
    from repro.engine import ForecastTwin
    from chipbench.harness.traffic import percentile
    twin = ForecastTwin(cfg, hardware.TPU_V5E,
                        Variant(kv_dtype="bf16", fused=True), em=TWIN_EM)
    fc = twin.replay(events)
    win = {r.idx for r in recs if r.in_window}
    f = [q for rid, q in fc.requests.items() if rid in win]
    m = [r for r in recs if r.in_window and r.n_tokens > 1]
    return {
        "device": "tpu-v5e", "em": TWIN_EM, "requests": len(f),
        "forecast_ttft_p50_ms": 1e3 * percentile([q.ttft for q in f], 50),
        "forecast_tpot_p50_ms": 1e3 * percentile(
            [q.tpot for q in f if q.n_tokens > 1], 50),
        "forecast_tps": fc.tps,
        "measured_ttft_excl_queue_p50_ms": 1e3 * percentile(
            [r.first - r.admitted for r in m], 50),
        "measured_tpot_p50_ms": 1e3 * percentile([r.tpot for r in m], 50)}


def run_cell(cell: spec.Cell, seed: int, seconds: float, trace: bool, *,
             peaks: dict, faults=None,
             control: bool = False, trace_dir=None) -> dict:
    """One run of ``cell``.  ``faults(engine)`` may break the timed path
    (tests); ``control`` also scores the fp8 control on the sample."""
    import jax
    import numpy as np
    from chipbench.harness import (check, engine_spans, loop, traffic,
                                   weights, xtrace)
    from chipbench.harness.compile_log import CompileLog
    from chipbench.harness.record import Run
    from repro.engine import Engine, EngineConfig
    from repro.launch.mesh import make_host_mesh
    from repro.runtime import ShardingPolicy

    enable_cache()
    log = CompileLog()
    a, ec, architecture = cell.arch, cell.config["engine"], cell.architecture
    cfg = architecture.arch_config(a)
    dev = jax.devices()[0]
    params = weights.make_params(architecture, a, seed)
    t_weights = time.perf_counter() - T_START
    engine = Engine(cfg, params, make_host_mesh(), ShardingPolicy(),
                    EngineConfig(**ec))
    del params
    engine.warmup()
    warm_slots(engine, ec)
    stats = dev.memory_stats() or {}
    _emit("setup", {"weights_ready_s": t_weights,
                    "warm_s": time.perf_counter() - T_START,
                    "weight_bytes": weights.param_bytes(architecture, a),
                    "bytes_in_use": stats.get("bytes_in_use"),
                    "bytes_limit": stats.get("bytes_limit"),
                    **log.snapshot()})
    gen = traffic.generate(cell.mix, seed, seconds, a["vocab_size"],
                           DRAIN_CAP_S, max_len=ec["max_len"])
    if faults is not None:
        faults(engine)
    tdir = None
    tr = None
    took = {}
    if trace:
        tdir = Path(trace_dir) if trace_dir else Path(tempfile.mkdtemp())

        def timed(name, fn):
            def call():
                t = time.perf_counter()
                fn()
                took[name] = time.perf_counter() - t
            return call
        tr = {"start": timed("trace_start_s",
                             lambda: jax.profiler.start_trace(str(tdir))),
              "stop": timed("trace_stop_s", jax.profiler.stop_trace)}
    before = log.snapshot()
    served = loop.serve(engine, gen, seconds, DRAIN_CAP_S, trace=tr)
    in_window = log.new_programs(before)
    stats = dev.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    events = list(engine.trace)
    out_tokens = {rid: list(res.tokens) for rid, res in
                  engine.results.items()}
    del engine
    gc.collect()

    recs = served.recs
    win = [r for r in recs if r.in_window]
    failed = [r for r in win if r.n_tokens == 0]
    lat = served.lateness or [0.0]
    done = [r for r in win if r.n_tokens > 1]
    ttft = [r.ttft for r in win if r.n_tokens]
    e2e = {
        "output_tps": served.tokens_in_window
        / (served.t_close - served.t_open),
        "ttft_p50_ms": 1e3 * traffic.percentile(ttft, 50),
        "ttft_p95_ms": 1e3 * traffic.percentile(ttft, 95),
        "tpot_p50_ms": 1e3 * traffic.percentile([r.tpot for r in done], 50),
        "tpot_p95_ms": 1e3 * traffic.percentile([r.tpot for r in done], 95),
        "setup_s": served.t_open - T_START,
    }
    _emit("window", {
        "setup_s": served.t_open - T_START,
        "window_s": served.t_close - served.t_open,
        "drain_s": max(0.0, max(s.t1 for s in served.steps)
                       - served.t_close),
        "drained": served.drained,
        "compiles_in_window": in_window,
        "generator_late_ms_p50": 1e3 * float(np.median(lat)),
        "generator_late_ms_p95": 1e3 * float(np.percentile(lat, 95)),
        "generator_late_ms_max": 1e3 * float(np.max(lat)),
        "requests_in_window": len(win),
        "finished_in_window": sum(r.finished for r in win),
        "steps": len(served.steps), **took,
        **{f"e2e_{k}": v for k, v in e2e.items()}})
    _emit("requests", {"ttft_ms": [round(1e3 * t, 1) for t in ttft],
                       "tpot_ms": [round(1e3 * r.tpot, 2) for r in done]})
    try:
        _emit("forecast", forecast(cfg, events, recs))
    except Exception as e:       # the forecast is a record, not a result
        _emit("forecast", {"error": repr(e)})

    run = Run(arch=a, architecture=architecture, engine=ec, peaks=peaks,
              served=served, events=events, trace=None)
    if trace:
        t0 = time.perf_counter()
        files = sorted(tdir.rglob("*.xplane.pb"))
        size = files[-1].stat().st_size
        run.trace = xtrace.reduce(xtrace.load(files[-1]))
        if trace_dir is None:
            shutil.rmtree(tdir, ignore_errors=True)
        _emit("trace", {"bytes": size, "reduce_s": time.perf_counter() - t0,
                        "ops": len(run.trace.devices[0].ops),
                        "engine_spans": len(run.trace.engine_spans)})
        _emit("engine_idle", engine_spans.engine_idle(
            run.trace, run.trace.engine_spans))
    metrics = {}
    if trace:
        for m in cell.per_layer:
            v = spec.reader(m["name"])(run)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}

    chk = cell.config["check"]
    picked = check.sample(recs, seed, chk["sample"])
    t0 = time.perf_counter()
    got = check.compare(cell.config, seed, picked, out_tokens,
                        ec["max_len"], control=control)
    got["seconds"] = time.perf_counter() - t0
    _emit("check", got)
    limit = chk["max_logit_gap"]
    compared = {"logit_gap": {"value": got["logit_gap"], "limit": limit},
                "failed": {"value": len(failed), "limit": 0}}
    correct = bool(picked) and got["logit_gap"] <= limit and not failed
    result = {"correct": correct, "attempted": len(win),
              "failed": len(failed), "metrics": metrics,
              "device": {"platform": dev.platform, "kind": dev.device_kind,
                         "count": cell.chips, "memory_peak_bytes": peak}}
    if control:
        result["control_logit_gap"] = got["control_logit_gap"]
    if trace:
        red = run.trace
        result["device"].update(busy_s=red.busy_s, window_s=red.window_s)
        result["breakdown"] = {
            "device_ops": [[n, s] for n, s in xtrace.op_time(red)],
            "idle_gaps": [[n, s] for n, s in xtrace.gap_totals(
                xtrace.label_gaps(red))]}
    result["check"] = compared
    return result


def _print_result(result: dict) -> None:
    for name, c in result["check"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(json.dumps(result), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--trace-dir", default=None,
                    help="keep the profiler trace here")
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)

    import jax
    from chipbench.harness import peaks as P
    if jax.default_backend() != "tpu":
        print(f"chipbench: no TPU (JAX backend is "
              f"{jax.default_backend()!r}); nothing was run", file=sys.stderr)
        return 2
    devices = jax.devices()
    if len(devices) < cell.chips:
        print(f"chipbench: {cell.name} needs {cell.chips} chips, found "
              f"{len(devices)}", file=sys.stderr)
        return 2
    peaks = P.for_kind(devices[0].device_kind)
    _emit("cell", {"workload": cell.name, "seed": args.seed,
                   "seconds": args.seconds, "trace": args.trace,
                   "platform": devices[0].platform,
                   "kind": devices[0].device_kind, "chips": cell.chips,
                   "visible": len(devices), "jax": jax.__version__})
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      peaks=peaks, trace_dir=args.trace_dir)
    _print_result(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
