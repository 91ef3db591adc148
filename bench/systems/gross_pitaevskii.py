"""The program's Gross-Pitaevskii solver, driven as
``examples/gross_pitaevskii.py`` runs it: ``jax.jit(make_step(grid, cfg))``
in a host loop, the fused coupled radius-2 ``@parallel`` launch."""
from __future__ import annotations

import os
import sys
import types


def _example():
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    path = os.path.join(root, "examples")
    if path not in sys.path:
        sys.path.insert(0, path)
    import gross_pitaevskii

    return gross_pitaevskii


def _step(ctx):
    gp = _example()
    cfg = ctx.cfg
    n = cfg["grid"][0]
    if cfg["grid"] != [n] * 3 or cfg["length"] != [cfg["length"][0]] * 3:
        raise ValueError("the example builds cubic grids only")
    gcfg = gp.GPConfig(n=n, g=cfg["g"], backend="pallas", fused=True,
                       interpret=ctx.interpret)
    grid = gp.make_grid(gcfg)
    if tuple(grid.length) != tuple(cfg["length"]):
        raise ValueError(f"the example's box is {grid.length}, the "
                         f"configuration's {cfg['length']}")
    return gp.make_step(grid, gcfg)


def jit_step(ctx):
    import jax

    step = jax.jit(_step(ctx))
    dt = ctx.p["dt"]

    def advance(s):
        re, im = step(s["re"], s["im"], dt, s["V"])
        return {"re": re, "im": im, "V": s["V"]}

    return types.SimpleNamespace(
        state=ctx.initial(), advance=advance, steps_per_call=1,
        outputs=lambda s: {"re": s["re"], "im": s["im"]})

