"""Seconds per solve to tolerance: the window's seconds over the solves
completed in it, each from the set-up's initial state and ending in its
host sync."""


def read(ctx):
    r = ctx.run
    if not r.get("solves"):
        return None
    return r["window_s"] / r["solves"]
