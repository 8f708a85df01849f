"""Runs one cell once, as bench/run.py does, and logs each chip's device
memory after each phase of the run, to find where `memory_peak_bytes` is
reached.

    python bench/memory.py --workload <cell> --seed <n> --seconds <s> --trace 0

To standard error, one line a phase: `MEM <phase> {"in_use": [...],
"peak": [...]}`, bytes chip by chip from `Device.memory_stats()`, after the
KV pool is placed, after the weights are drawn, after every shape is warmed
and when the window has closed (before the reference check).  Standard
output is bench/run.py's."""
from __future__ import annotations

import functools
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
for p in (BENCH, BENCH.parent / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

import run  # noqa: E402  (its import starts the set-up clock)

PHASES = ("pool placed", "weights drawn", "shapes warmed", "window closed")


def log_memory(phase: str) -> None:
    import jax

    stats = [d.memory_stats() or {} for d in jax.devices()]
    run.log(f"MEM {phase} " + json.dumps({
        "in_use": [s.get("bytes_in_use") for s in stats],
        "peak": [s.get("peak_bytes_in_use") for s in stats]}))


def _after(phase, fn):
    @functools.wraps(fn)
    def wrapped(*args, **kw):
        import jax

        out = jax.block_until_ready(fn(*args, **kw))
        log_memory(phase)
        return out

    return wrapped


def _before(phase, fn):
    @functools.wraps(fn)
    def wrapped(*args, **kw):
        log_memory(phase)
        return fn(*args, **kw)

    return wrapped


def main(argv=None, **kw) -> dict:
    """bench/run.py's run with the phases logged; ``kw`` as run.main takes
    it."""
    from mxbench import check, weights
    from repro.launch import serve

    hooks = [(serve, "place", _after, PHASES[0]),
             (weights, "make", _after, PHASES[1]),
             (run, "warm_up", _after, PHASES[2]),
             (check, "readings", _before, PHASES[3])]
    plain = [getattr(mod, name) for mod, name, _, _ in hooks]
    for (mod, name, wrap, phase), fn in zip(hooks, plain):
        setattr(mod, name, wrap(phase, fn))
    try:
        return run.main(argv, **kw)
    finally:
        for (mod, name, _, _), fn in zip(hooks, plain):
            setattr(mod, name, fn)


if __name__ == "__main__":
    try:
        main()
    except run.Refused as e:
        print(f"bench/memory.py: {e}", file=sys.stderr)
        sys.exit(3)
