#!/usr/bin/env python3
"""Find an open-loop cell's knee: the highest arrival rate at which the
requests in flight do not pile up over the window.

    python3 chipbench/sweep.py --workload <cell> --seed <n> --seconds <s> \\
        --rates 1.0,1.5,2.0,...

One process builds the cell's engine once, then offers the cell's mix at
each rate (after the mix's own ``warm_s`` of load) for ``--seconds`` and
prints one line per rate: the requests in flight (queued or running) at
the window's start and end and their least-squares slope over the
window, the queue alone likewise, requests completed per second, and
the TTFT and TPOT tails.  The knee is the highest rate up to which every
rate's in-flight slope is under ``--slope`` requests per second: the
number in flight swings by a few slots from step to step, so a slope
under that is noise, and one above it a pile that grows.  A cell then offers 0.8 of it; its rate
is written into the mix file by hand, with this output in ``PERF.md``.
Needs the chip, like ``run.py``.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

from chipbench import run as R  # noqa: E402
from chipbench.harness import loop, spec, traffic, weights  # noqa: E402


class _Probe:
    """Samples the queue and the running requests at every engine step."""

    def __init__(self, engine):
        self.samples = []
        step = engine.step

        def probed():
            self.samples.append((time.perf_counter(), len(engine.queue),
                                 len(engine.running)))
            step()
        engine.step = probed


def _slope(xs, ys):
    import numpy as np
    if len(xs) < 2:
        return 0.0
    return float(np.polyfit(np.asarray(xs) - xs[0], ys, 1)[0])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--slope", type=float, default=0.1)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)
    if cell.mix["arrival"]["kind"] == "closed":
        raise SystemExit("a knee is found for an open-loop mix")
    import jax
    if jax.default_backend() != "tpu":
        print("sweep: no TPU", file=sys.stderr)
        return 2
    from repro.engine import Engine, EngineConfig
    from repro.launch.mesh import make_host_mesh
    from repro.runtime import ShardingPolicy
    R.enable_cache()
    a, ec, architecture = cell.arch, cell.config["engine"], cell.architecture
    engine = Engine(architecture.arch_config(a),
                    weights.make_params(architecture, a, args.seed),
                    make_host_mesh(), ShardingPolicy(), EngineConfig(**ec))
    engine.warmup()
    R.warm_slots(engine, ec)
    rows = []
    for rate in [float(x) for x in args.rates.split(",")]:
        mix = dict(cell.mix, arrival=dict(cell.mix["arrival"],
                                          rate_per_s=rate))
        gen = traffic.generate(mix, args.seed, args.seconds, a["vocab_size"],
                               args.seconds, max_len=ec["max_len"])
        probe = _Probe(engine)
        served = loop.serve(engine, gen, args.seconds, 0.0)
        del engine.step
        engine.queue.clear()
        engine.run()
        engine.reset_metrics()
        win = [s for s in probe.samples
               if served.t_open <= s[0] <= served.t_close]
        recs = [r for r in served.recs if r.in_window]
        got = [r for r in recs if r.n_tokens]
        ts = [s[0] for s in win]
        row = {
            "rate_per_s": rate,
            "in_flight_slope_per_s": _slope(ts, [s[1] + s[2] for s in win]),
            "in_flight_start": win[0][1] + win[0][2] if win else 0,
            "in_flight_end": win[-1][1] + win[-1][2] if win else 0,
            "queue_slope_per_s": _slope(ts, [s[1] for s in win]),
            "queue_end": win[-1][1] if win else 0,
            "due": len(recs),
            "completed_per_s": sum(
                r.finished and served.t_open <= r.last < served.t_close
                for r in served.recs) / (served.t_close - served.t_open),
            "output_tps": served.tokens_in_window
            / (served.t_close - served.t_open),
            "ttft_p50_ms": 1e3 * traffic.percentile([r.ttft for r in got], 50),
            "ttft_p95_ms": 1e3 * traffic.percentile([r.ttft for r in got], 95),
            "tpot_p95_ms": 1e3 * traffic.percentile(
                [r.tpot for r in got if r.n_tokens > 1], 95)}
        rows.append(row)
        print(json.dumps(row), flush=True)
    knee = None
    for r in sorted(rows, key=lambda r: r["rate_per_s"]):
        if r["in_flight_slope_per_s"] >= args.slope:
            break
        knee = r["rate_per_s"]
    summary = {"workload": cell.name, "seed": args.seed,
               "seconds": args.seconds, "knee_per_s": knee,
               "cell_rate_per_s": None if knee is None else 0.8 * knee,
               "rows": rows}
    print(json.dumps({k: v for k, v in summary.items() if k != "rows"}))
    if args.out:
        Path(args.out).write_text(json.dumps(summary, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
