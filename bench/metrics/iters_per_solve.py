"""Steps per solve to tolerance: ``SolveResult.iters`` of every solve in
the window, averaged (a count the solve loop reports at its host sync)."""


def read(ctx):
    iters = ctx.run.get("iters")
    if not iters:
        return None
    return sum(iters) / len(iters)
