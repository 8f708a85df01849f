"""Collective operations inside the decode program, from the reduced trace.

A collective is a device op whose base name starts with one of
`COLLECTIVES` (the TPU runs a ring's `ppermute` as `collective-permute`,
started and then waited for).  Its exposed part is the time in which it
runs and no other op computes: the union of the collective intervals less
what the union of the compute ops covers.  Control-flow ops, which the
trace draws around the ops they run (a loop over the layers holds its
body), are left out of the compute side, or a loop would hide every
collective in it."""
from __future__ import annotations

from typing import Optional

from . import devtrace
from .view import DECODE_PROGRAM

COLLECTIVES = ("collective-permute", "all-gather", "all-reduce",
               "reduce-scatter", "all-to-all")
CONTROL_FLOW = ("while", "conditional", "call")


def is_collective(name: str) -> bool:
    return devtrace.base_name(name).startswith(COLLECTIVES)


def is_compute(name: str) -> bool:
    base = devtrace.base_name(name)
    return base not in CONTROL_FLOW and not base.startswith(COLLECTIVES)


def per_step(run, exposed: bool) -> Optional[float]:
    """Milliseconds of collective time (``exposed``: of its exposed part)
    per execution of the decode program, averaged over the chips; None
    where the trace holds no decode program."""
    per_dev = []
    for dev in run.devices():
        runs = run.program_runs(dev, DECODE_PROGRAM)
        if not runs:
            continue
        # a program's ops lie inside its execution, and executions do not
        # overlap, so one union over all of them sums the per-run unions
        ops = devtrace.inside(dev.ops, runs)
        lo, hi = min(s for _, s, _ in runs), max(e for _, _, e in runs)
        coll = [(s, e) for n, s, e in ops if is_collective(n)]
        total = devtrace.union_ns(coll, lo, hi)
        if exposed:
            comp = [(s, e) for n, s, e in ops if is_compute(n)]
            total = (devtrace.union_ns(coll + comp, lo, hi)
                     - devtrace.union_ns(comp, lo, hi))
        per_dev.append(total / len(runs))
    return 1e-6 * sum(per_dev) / len(per_dev) if per_dev else None
