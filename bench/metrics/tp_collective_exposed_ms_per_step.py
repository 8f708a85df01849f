"""The part of the decode program's collective time that no compute op
overlaps, per execution, averaged over the chips: what the rings leave
exposed.  Layer: collectives.  Moves itl_p50_ms."""
from mxbench import collectives


def read(run):
    return collectives.per_step(run, exposed=True)
