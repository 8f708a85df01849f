"""What a per-layer metric reader is given: the run's configuration, peaks,
window records, counters and (in a `--trace 1` run) the reduced trace, with
the joins the readers share."""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Tuple

from . import devtrace
from .loop import Window

DECODE_PROGRAM = "step_paged"      # the batcher's jitted decode step
PREFILL_PROGRAM = "prefill_paged"  # the batcher's jitted chunked prefill


@dataclasses.dataclass
class RunView:
    c: dict                      # the configuration file
    chips: int
    peak: Dict[str, float]
    win: Window
    compiles_in_window: int
    trace: Optional[devtrace.Trace] = None
    traced: Tuple[int, int] = (0, 0)   # [first, last) step indices traced

    # -- window ----------------------------------------------------------

    def tokens_by_step(self) -> Dict[float, List[Tuple[int, int]]]:
        """step end time -> [(rid, j)]: the j-th answer token of request
        rid was emitted by the step that ended then."""
        out: Dict[float, List[Tuple[int, int]]] = {}
        for r in self.win.records.values():
            for j, t in enumerate(r.times):
                out.setdefault(t, []).append((r.rid, j))
        return out

    def traced_steps(self):
        return self.win.steps[self.traced[0]:self.traced[1]]

    # -- trace -----------------------------------------------------------

    def devices(self) -> List[devtrace.Device]:
        return list(self.trace.devices.values()) if self.trace else []

    def program_runs(self, dev: devtrace.Device, fragment: str):
        lo, hi = devtrace.window(self.trace)
        return [m for m in devtrace.modules_named(dev, fragment)
                if lo <= (m[1] + m[2]) // 2 <= hi]

    def kernel_in_program(self, dev: devtrace.Device, kernel: str,
                          fragment: str):
        """Events of ``kernel`` inside executions of the program whose
        name holds ``fragment``, in the traced window."""
        return devtrace.inside(devtrace.ops_named(dev.ops, kernel),
                               self.program_runs(dev, fragment))
