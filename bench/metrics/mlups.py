"""Grid-point updates per second over the whole window, in millions:
the points inside the ring a step never writes, summed over chips,
times the steps completed in the window, over the window's seconds."""


def read(ctx):
    r = ctx.run
    if not r.get("steps") or not r.get("window_s"):
        return None
    points = 1
    for n in ctx.cfg["grid"]:
        points *= n - 2 * ctx.ring
    return points * r["steps"] / r["window_s"] / 1e6
