"""Benchmark driver: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV.  ``us_per_call`` is the wall time
of the LIFE simulation that produced the row (the paper's point: full
workload characterization runs in seconds on a laptop); ``derived`` packs
the reproduced metrics next to the paper's published values.

Modules may expose ``bench_artifact(rows) -> dict``; the driver then
writes ``BENCH_<shortname>.json`` (e.g. ``BENCH_engine.json`` from
``engine_throughput``) so the perf trajectory is tracked across PRs, and
appends the same payload — stamped with the git sha and date — as one
line of ``BENCH_history.jsonl``, the append-only cross-PR trajectory.

    python -m benchmarks.run                       # everything
    python -m benchmarks.run --only engine_throughput
    python -m benchmarks.run table4_prefill_ops roofline
"""
import argparse
import datetime
import importlib
import json
import os
import subprocess
import sys
import time

MODULES = [
    "operator_workloads",
    "table4_prefill_ops",
    "table5_variant_metrics",
    "fig3_variant_breakdown",
    "fig4_efficiency_grid",
    "table6_prefill_forecast",
    "fig6_chunked_prefill",
    "table7_decode_metrics",
    "table8_dispatch_calls",
    "table9_decode_memory",
    "table10_decode_forecast",
    "table11_attention_memory",
    "fig8_bmm_tiling",
    "table12_lora",
    "xval_life_vs_xla",
    "roofline",
    "engine_throughput",
]


def _previous_payload(hist_path: str, modname: str):
    """Last BENCH_history entry for ``modname``, or None."""
    if not os.path.exists(hist_path):
        return None
    prev = None
    with open(hist_path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                rec = json.loads(line)
            except json.JSONDecodeError:
                continue
            if rec.get("module") == modname:
                prev = rec
    return prev


def _forecast_error_regression(prev, payload):
    """Error message if the calibrated-host forecast error regressed vs
    the previous BENCH_history entry, else None.

    The gate compares ``forecast_error.worst_abs`` (largest |signed TPS
    error| across settings on the ``host-cpu`` spec) and tolerates noise:
    fail only when the new worst error exceeds the previous by more than
    25% relative AND 2 percentage points absolute.
    """
    new = (payload.get("forecast_error") or {}).get("worst_abs")
    old = ((prev or {}).get("forecast_error") or {}).get("worst_abs")
    if new is None or old is None:
        return None
    if new > old * 1.25 and new > old + 0.02:
        return (f"forecast error regressed on {payload.get('benchmark')}: "
                f"worst |rel err| {old:.3f} -> {new:.3f} on "
                f"{payload['forecast_error'].get('hardware')} "
                f"(prev sha {prev.get('git_sha')})")
    return None


def _git_sha() -> str:
    try:
        out = subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"],
            capture_output=True, text=True, timeout=10,
            cwd=os.path.dirname(os.path.abspath(__file__)))
        return out.stdout.strip() or "unknown"
    except (OSError, subprocess.SubprocessError):
        return "unknown"


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("modules", nargs="*",
                    help="benchmark modules to run (default: all)")
    ap.add_argument("--only", default=None,
                    help="comma-separated module subset (same as positional)")
    ap.add_argument("--artifact-dir", default=".",
                    help="where BENCH_*.json artifacts are written")
    ap.add_argument("--gate-forecast-error", action="store_true",
                    help="exit nonzero if a module's calibrated-host "
                         "forecast error regressed vs its previous "
                         "BENCH_history.jsonl entry (the CI accuracy gate)")
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    only = list(args.modules)
    if args.only:
        only += [m for m in args.only.split(",") if m]
    only = only or None
    if only:
        unknown = [m for m in only if m not in MODULES]
        if unknown:
            print(f"unknown benchmark module(s): {', '.join(unknown)}; "
                  f"known: {', '.join(MODULES)}", file=sys.stderr)
            sys.exit(2)
    failed = []
    regressions = []
    print("name,us_per_call,derived")
    for modname in MODULES:
        if only and modname not in only:
            continue
        mod = importlib.import_module(f"benchmarks.{modname}")
        t0 = time.perf_counter()
        try:
            rows = mod.rows()
        except Exception as e:  # surface but keep the suite going
            print(f"{modname},0,\"ERROR: {type(e).__name__}: {e}\"")
            failed.append(modname)
            continue
        elapsed_us = (time.perf_counter() - t0) * 1e6
        per_row = elapsed_us / max(len(rows), 1)
        for name, derived in rows:
            payload = json.dumps(derived, separators=(",", ":")).replace('"', "'")
            print(f"{name},{per_row:.1f},\"{payload}\"")
        artifact_fn = getattr(mod, "bench_artifact", None)
        if artifact_fn is not None:
            short = modname.split("_")[0]
            path = os.path.join(args.artifact_dir, f"BENCH_{short}.json")
            payload = artifact_fn(rows)
            with open(path, "w") as f:
                json.dump(payload, f, indent=1)
                f.write("\n")
            print(f"wrote {path}", file=sys.stderr)
            hist = os.path.join(args.artifact_dir, "BENCH_history.jsonl")
            prev = _previous_payload(hist, modname)
            msg = _forecast_error_regression(prev, payload)
            if msg:
                print(msg, file=sys.stderr)
                if args.gate_forecast_error:
                    regressions.append(msg)
            record = {
                "date": datetime.datetime.now(
                    datetime.timezone.utc).isoformat(timespec="seconds"),
                "git_sha": _git_sha(),
                "module": modname,
                **payload,
            }
            with open(hist, "a") as f:
                f.write(json.dumps(record, separators=(",", ":")) + "\n")
            print(f"appended {hist}", file=sys.stderr)
    if failed:
        print(f"{len(failed)} benchmark module(s) failed: "
              f"{', '.join(failed)}", file=sys.stderr)
        sys.exit(1)
    if regressions:
        print(f"{len(regressions)} forecast-error regression(s) — see "
              f"above", file=sys.stderr)
        sys.exit(3)


if __name__ == "__main__":
    main()
