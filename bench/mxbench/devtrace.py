"""The profiler's trace, reduced: device op intervals per chip, the
program (XLA module) executions they belong to, and the harness's host
spans on the same clock.

`load(dir)` reads the `.xplane.pb` that `jax.profiler` wrote, with nothing
but JAX.  Everything else works on plain (name, start_ns, end_ns) tuples, so
the tests drive it with synthetic traces.  The traced window is the host
span `bench.traced`."""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

Event = Tuple[str, int, int]   # (name, start_ns, end_ns)

_SUFFIX = re.compile(r"[.\d]+$")


def base_name(name: str) -> str:
    """An HLO op or module name without its numeric suffix: the TPU's
    trace names an op by its HLO text, "%mx_matmul_fused.12 = f32[8,128]
    custom-call(...)" -> "mx_matmul_fused"; "jit_step_paged(3)" ->
    "jit_step_paged"."""
    name = name.split(" = ", 1)[0].lstrip("%")
    return _SUFFIX.sub("", name.split("(")[0])


@dataclasses.dataclass
class Device:
    ops: List[Event]          # device ops ("XLA Ops" line)
    modules: List[Event]      # program executions ("XLA Modules" line)


@dataclasses.dataclass
class Trace:
    devices: Dict[str, Device]
    spans: List[Event]        # harness host spans (bench.*)

    def span(self, name: str) -> Optional[Event]:
        hits = [s for s in self.spans if s[0] == name]
        return (name, min(s[1] for s in hits), max(s[2] for s in hits)) \
            if hits else None


def union_ns(intervals: Sequence[Tuple[int, int]], lo: int, hi: int) -> int:
    """Length of the union of intervals, clipped to [lo, hi]."""
    total, cur_s, cur_e = 0, None, None
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps(intervals: Sequence[Tuple[int, int]], lo: int, hi: int
         ) -> List[Tuple[int, int]]:
    """The idle stretches of [lo, hi] that no interval covers."""
    out, t = [], lo
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def inside(events: Sequence[Event], outer: Sequence[Event]) -> List[Event]:
    """Events whose midpoint lies inside one of ``outer``."""
    spans = sorted((s, e) for _, s, e in outer)
    starts = [s for s, _ in spans]
    out = []
    for ev in events:
        mid = (ev[1] + ev[2]) // 2
        i = bisect.bisect_right(starts, mid) - 1
        if i >= 0 and spans[i][1] >= mid:
            out.append(ev)
    return out


def modules_named(dev: Device, fragment: str) -> List[Event]:
    return [m for m in dev.modules if fragment in m[0]]


def ops_named(events: Sequence[Event], kernel: str) -> List[Event]:
    return [e for e in events if base_name(e[0]) == kernel]


def total_s(events: Sequence[Event]) -> float:
    return sum(e - s for _, s, e in events) * 1e-9


def window(trace: Trace) -> Optional[Tuple[int, int]]:
    w = trace.span("bench.traced")
    return (w[1], w[2]) if w else None


def busy(trace: Trace) -> Dict[str, float]:
    """Seconds of the traced window in which some op ran, per device."""
    lo, hi = window(trace)
    return {name: union_ns([(s, e) for _, s, e in d.ops], lo, hi) * 1e-9
            for name, d in trace.devices.items()}


def host_span_at(trace: Trace, t: int) -> str:
    """The innermost harness span covering host time t."""
    best = None
    for name, s, e in trace.spans:
        if name != "bench.traced" and s <= t <= e:
            if best is None or e - s < best[2] - best[1]:
                best = (name, s, e)
    return best[0] if best else "outside"


def idle_gaps(trace: Trace, device: str, top: int = 10
              ) -> List[Tuple[str, float]]:
    """The longest idle stretches of ``device`` in the traced window, each
    named by what the host was doing at its midpoint."""
    lo, hi = window(trace)
    d = trace.devices[device]
    g = sorted(gaps([(s, e) for _, s, e in d.ops], lo, hi),
               key=lambda ge: ge[0] - ge[1])[:top]
    return [(host_span_at(trace, (s + e) // 2), (e - s) * 1e-9) for s, e in g]


def self_ns(events: Sequence[Event]) -> List[Tuple[str, int]]:
    """(name, self time) of each event: its length less the events nested
    in it (the trace puts a loop's body ops inside the loop op)."""
    out: List[list] = []
    stack: List[Tuple[int, int]] = []     # (index into out, end)
    for name, s, e in sorted(events, key=lambda ev: (ev[1], -ev[2])):
        while stack and stack[-1][1] <= s:
            stack.pop()
        out.append([name, e - s])
        if stack and e <= stack[-1][1]:
            out[stack[-1][0]][1] -= e - s
        stack.append((len(out) - 1, e))
    return [(n, t) for n, t in out]


def top_ops(trace: Trace, top: int = 10) -> List[Tuple[str, float]]:
    """Device ops by self time in the window, averaged over devices."""
    lo, hi = window(trace)
    tot: Dict[str, float] = {}
    for d in trace.devices.values():
        clipped = [(n, max(s, lo), min(e, hi)) for n, s, e in d.ops
                   if min(e, hi) > max(s, lo)]
        for name, t in self_ns(clipped):
            key = base_name(name)
            tot[key] = tot.get(key, 0.0) + t * 1e-9
    n = max(1, len(trace.devices))
    return sorted(((k, v / n) for k, v in tot.items()),
                  key=lambda kv: -kv[1])[:top]


# ---------------------------------------------------------------------------
# reading the profiler's files
# ---------------------------------------------------------------------------

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def load(log_dir: str) -> Trace:
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    devices: Dict[str, Device] = {}
    spans: List[Event] = []
    for path in paths:
        pd = ProfileData.from_file(path)
        for plane in pd.planes:
            if plane.name.startswith("/device:TPU:") and \
                    plane.name[len("/device:TPU:"):].isdigit():
                ops, mods = [], []
                for line in plane.lines:
                    evs = [(e.name, int(e.start_ns),
                            int(e.start_ns + e.duration_ns))
                           for e in line.events]
                    if line.name == OPS_LINE:
                        ops.extend(evs)
                    elif line.name == MODULES_LINE:
                        mods.extend(evs)
                devices[plane.name] = Device(ops, mods)
            elif plane.name.startswith("/host:"):
                for line in plane.lines:
                    for e in line.events:
                        if e.name.startswith("bench."):
                            spans.append((e.name, int(e.start_ns),
                                          int(e.start_ns + e.duration_ns)))
    return Trace(devices, spans)
