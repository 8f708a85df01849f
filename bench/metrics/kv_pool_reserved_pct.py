"""Mean share of the KV pool's pages reserved by live requests over the
window's steps (the page pool's own counter; admission reserves each
request's prompt plus its whole answer).  Layer: KV pool.  Moves
output_tok_s: a full pool holds admissions back."""


def read(run):
    steps = run.win.window_steps()
    if not steps:
        return None
    return 100.0 * sum(s.reserved for s in steps) / len(steps)
