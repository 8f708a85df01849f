"""Device time of the chunked-prefill program per 1000 prompt tokens it
processed in the traced steps (each admitted prompt but its last token,
which rides the decode step).  Layer: model step.  Moves ttft_p95_ms."""
from mxbench import devtrace
from mxbench.view import PREFILL_PROGRAM


def read(run):
    ends = {s.end for s in run.traced_steps()}
    tokens = sum(r.prompt_len - 1 for r in run.win.records.values()
                 if r.first in ends)
    devs = run.devices()
    if not tokens or not devs:
        return None
    t = sum(devtrace.total_s(run.program_runs(d, PREFILL_PROGRAM))
            for d in devs) / len(devs)
    return 1e3 * t / (tokens / 1e3) if t > 0 else None
