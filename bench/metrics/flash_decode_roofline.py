"""Share of the roofline reached by the paged decode-attention kernel:
roofline time of the live K/V rows each traced step reads (from the
harness's own token counts) over the kernel's summed device time.  HBM
bytes bound it.  Layer: kernels.  Moves itl_p50_ms."""
from mxbench import devtrace, work
from mxbench.view import DECODE_PROGRAM

KERNEL = "mx_flash_decode"


def read(run):
    devs = run.devices()
    steps = run.traced_steps()
    if not devs or not steps:
        return None
    by_step = run.tokens_by_step()
    prompt = {r.rid: r.prompt_len for r in run.win.records.values()}
    need = 0.0
    for s in steps:
        emitted = by_step.get(s.end, [])
        live = sum(prompt[rid] + j for rid, j in emitted)
        f, b = work.flash_decode(run.c, live, len(emitted))
        need += work.roofline_s(f / run.chips, b / run.chips, run.peak)
    shares = []
    for dev in devs:
        t = devtrace.total_s(run.kernel_in_program(dev, KERNEL,
                                                   DECODE_PROGRAM))
        if t > 0:
            shares.append(need / t)
    return 100.0 * sum(shares) / len(shares) if shares and need else None
