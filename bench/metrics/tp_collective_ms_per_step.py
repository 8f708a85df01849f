"""Device time of the collective operations (the TP rings' hops) inside
each execution of the decode program, averaged over the chips.  Layer:
collectives.  Moves itl_p50_ms."""
from mxbench import collectives


def read(run):
    return collectives.per_step(run, exposed=False)
