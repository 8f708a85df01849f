"""Mean device time of one execution of the decode program, from the
trace, averaged over the chips.  Layer: model step.  Moves itl_p50_ms."""
from mxbench import devtrace
from mxbench.view import DECODE_PROGRAM


def read(run):
    times = [devtrace.total_s(runs) / len(runs) for dev in run.devices()
             if (runs := run.program_runs(dev, DECODE_PROGRAM))]
    return 1e3 * sum(times) / len(times) if times else None
