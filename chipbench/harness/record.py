"""What one run recorded, as the per-layer metric readers see it."""
from __future__ import annotations

import dataclasses
from types import ModuleType
from typing import Dict, List, Optional

from .loop import Served
from .xtrace import Reduced


@dataclasses.dataclass
class Run:
    arch: Dict                  # the configuration's model sizes
    architecture: ModuleType    # its module (chipbench/arch): the counts
    engine: Dict                # the configuration's engine settings
    peaks: Dict                 # the chip's published peaks
    served: Served              # the harness's records of the window
    events: List                # the engine's TraceEvents of the run
    trace: Optional[Reduced]    # the reduced profiler trace (--trace 1)

    @property
    def kv_bytes(self) -> int:
        return {"bf16": 2, "int8": 1}[self.engine["kv_dtype"]]

    def window_steps(self):
        s = self.served
        return [st for st in s.steps
                if st.t0 >= s.t_open and st.t1 <= s.t_close]

    def window_events(self, kind: str):
        return [self.events[i] for st in self.window_steps()
                for i in range(st.ev_lo, st.ev_hi)
                if self.events[i].kind == kind]

    def window_recs(self):
        return [r for r in self.served.recs if r.in_window]
