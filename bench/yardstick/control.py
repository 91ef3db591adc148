"""The control of the comparison: the plain reference put in the
program's place, computed in the nearest precision below the one the
configuration states (bfloat16 for float32). A comparison that does not
fail the control cannot tell a lower-precision program from a sound one.

The control runs the cell's own traffic at the cell's own size, through
the same driver, window and comparison as the program."""
from __future__ import annotations

import jax.numpy as jnp

from . import compare

BELOW = {"float32": jnp.bfloat16, "float64": jnp.float32}


def install(entry, ctx, sharding=None) -> None:
    """Replace ``entry``'s timed path with the reference at the precision
    below; the outputs become the reference's whole global fields."""
    low = compare.Reference(ctx.ref, ctx.cfg, ctx.p,
                            BELOW[ctx.cfg["dtype"]], sharding)
    entry.state = low.initial()
    if hasattr(entry, "solve"):
        entry.solve = lambda _: low.solve(ctx.traffic)
    else:
        k = entry.steps_per_call
        entry.advance = lambda s: low.steps(s, k)
    entry.outputs = lambda s: {k: s[k] for k in ctx.ref.OUTPUTS}
    entry.interior_only = False
