"""Whole decode step's share of the chips' peak: the model FLOPs of the
answer tokens the traced steps emitted (the block weights, attention over
each token's live context, the LM head) over the decode program's summed
device time x chips x peak.  It bounds the decode kernels' rooflines: a
kernel taken off the path leaves its own roofline silent, not this.
Layer: device, whole step.  Moves itl_p50_ms."""
from mxbench import devtrace, work
from mxbench.view import DECODE_PROGRAM


def read(run):
    devs = run.devices()
    steps = run.traced_steps()
    if not devs or not steps:
        return None
    by_step = run.tokens_by_step()
    prompt = {r.rid: r.prompt_len for r in run.win.records.values()}
    flops = sum(work.token_flops(run.c, prompt[rid] - 1 + j, True)
                for s in steps for rid, j in by_step.get(s.end, []))
    times = [devtrace.total_s(runs) for dev in devs
             if (runs := run.program_runs(dev, DECODE_PROGRAM))]
    t = sum(times) / len(times) if times else 0.0
    if not flops or t <= 0:
        return None
    return 100.0 * flops / (t * run.chips * run.peak["flops_per_s"])
