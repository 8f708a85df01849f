"""Whole-step share of the chips' peak: the model FLOPs the window's work
needs (every admitted prompt through the blocks, every later answer token
through the blocks, attention over the live context, the LM head once per
answer token) over window x chips x peak.  Layer: device, whole step.
Moves output_tok_s."""
from mxbench import work


def read(run):
    w = run.win
    flops = 0.0
    for r in w.records.values():
        if r.first is not None and w.in_window(r.first):
            flops += work.prompt_flops(run.c, r.prompt_len)
        for j, t in enumerate(r.times[1:], start=1):
            if w.in_window(t):
                flops += work.token_flops(run.c, r.prompt_len - 1 + j, True)
    if not flops:
        return None
    return 100.0 * flops / (w.seconds * run.chips * run.peak["flops_per_s"])
