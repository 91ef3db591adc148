"""The share of the traced window in which a collective (the halo
exchange's permutes, the check's reductions) runs on a chip and no
compute does, averaged over the chips."""
from yardstick import trace


def read(ctx):
    return trace.exposed_collective_share(ctx.trace)
