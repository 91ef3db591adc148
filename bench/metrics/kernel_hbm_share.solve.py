"""Stencil launches' share of the HBM roofline in a solve-to-tolerance cell (plain and checked launches alike): the
bytes of each launch's operands and results, each counted once, over the
chip's HBM bandwidth times the launches' device time (``yardstick/trace.py``
finds the launches and their bytes)."""
from yardstick import trace


def read(ctx):
    return trace.hbm_share(ctx.trace, ctx.kind)
