#!/usr/bin/env python3
"""The two readings that a cell's check limit is set between.

    python3 chipbench/readings.py --workload <cell> --seeds 1,2,... \\
        --control-seeds 1,2,3 --seconds <s>

In one process, for each seed, one run of the cell as ``run.py`` makes
it (weights from the seed, the engine, the cell's own load for
``--seconds``, the sample of finished requests) and the widest logit gap
of the served tokens against the float32 reference: the lower reading is
the largest over the seeds.  On the control seeds the same sample is
also scored with the reference in fp8 (``reference/*.py``, ``mode="fp8"``):
the upper reading is the smallest control gap.  One JSON line per seed,
then a summary.  Needs the chip.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

from chipbench import run as R  # noqa: E402
from chipbench.harness import peaks as P, spec  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)
    import jax
    if jax.default_backend() != "tpu":
        print("readings: no TPU", file=sys.stderr)
        return 2
    peaks = P.for_kind(jax.devices()[0].device_kind)
    ctl = {int(s) for s in args.control_seeds.split(",") if s}
    lower, upper = [], []
    for s in [int(x) for x in args.seeds.split(",")]:
        r = R.run_cell(cell, s, args.seconds, False, peaks=peaks,
                       control=s in ctl)
        row = {"seed": s, "logit_gap": r["check"]["logit_gap"]["value"],
               "correct": r["correct"], "attempted": r["attempted"],
               "failed": r["failed"]}
        lower.append(row["logit_gap"])
        if s in ctl:
            row["control_logit_gap"] = r["control_logit_gap"]
            upper.append(r["control_logit_gap"])
        print(json.dumps(row), flush=True)
    print(json.dumps({"workload": cell.name, "lower": max(lower),
                      "upper": min(upper) if upper else None,
                      "program": sorted(lower), "control": sorted(upper)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
