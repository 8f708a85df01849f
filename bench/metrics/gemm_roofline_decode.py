"""Share of the roofline reached by the fused GEMM kernel inside decode
programs: the sum over traced decode steps of each launch's roofline time
(its operations and bytes at M = slots, from the configuration) over the
kernel's summed device time.  HBM bytes bound every decode GEMM at these
shapes.  Layer: kernels.  Moves itl_p50_ms."""
from mxbench import devtrace, work
from mxbench.view import DECODE_PROGRAM

KERNEL = "mx_matmul_fused"


def read(run):
    devs = run.devices()
    if not devs:
        return None
    per_step = sum(work.roofline_s(f / run.chips, b / run.chips, run.peak)
                   for f, b in work.decode_gemms(run.c, run.c["slots"]))
    shares = []
    for dev in devs:
        n = len(run.program_runs(dev, DECODE_PROGRAM))
        t = devtrace.total_s(run.kernel_in_program(dev, KERNEL,
                                                   DECODE_PROGRAM))
        if n and t > 0:
            shares.append(per_step * n / t)
    return 100.0 * sum(shares) / len(shares) if shares else None
