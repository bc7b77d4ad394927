"""A cell small enough for the CPU: the tests drive ``run.run_cell`` with
it, past the harness's look for a chip."""
from __future__ import annotations

import json
from pathlib import Path

from chipbench.harness import spec

DATA = Path(__file__).resolve().parent / "data"

#: stand-in peaks for CPU runs (the rooflines read from them are never
#: reported; only a chip run reports device numbers)
PEAKS = {"name": "cpu-test", "bf16_flops_per_s": 1e12,
         "hbm_bytes_per_s": 1e11, "hbm_bytes": 1e9}


def cell(mix: str = "tiny-open") -> spec.Cell:
    bench = json.loads((spec.ROOT / "BENCHMARK.json").read_text())
    return spec.Cell(
        name=f"tiny.{mix}", chips=1,
        config=json.loads((DATA / "tiny.json").read_text()),
        mix=json.loads((DATA / f"{mix}.json").read_text()),
        end_to_end=bench["end_to_end"], per_layer=bench["per_layer"])
