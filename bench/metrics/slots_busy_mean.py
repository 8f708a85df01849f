"""Mean busy decode slots over the window's steps (`StepHealth.active`,
the batcher's own counter).  Layer: scheduler.  Moves output_tok_s."""


def read(run):
    steps = run.win.window_steps()
    if not steps:
        return None
    return sum(s.active for s in steps) / len(steps)
