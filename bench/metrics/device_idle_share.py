"""Share of the traced window in which no operation ran on the device:
1 - (union of device-op intervals / window), averaged over the chips.
Layer: device.  Moves itl_p50_ms."""
from mxbench import devtrace


def read(run):
    if not run.trace or not run.trace.devices:
        return None
    lo, hi = devtrace.window(run.trace)
    busy = devtrace.busy(run.trace)
    return 100.0 * (1.0 - sum(busy.values()) / len(busy) / ((hi - lo) * 1e-9))
