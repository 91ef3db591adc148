"""Solve-to-tolerance traffic: one solve after another, each from the
set-up's initial state and ending in its host sync, for as long as the
window lasts. The window closes at the end of the first solve that
finishes after ``seconds``. The reference solves once from the same
state; every solve of the window has to stop where it stops."""
from __future__ import annotations

import time


def _solve(entry, state):
    fields, iters, err = entry.solve(state)
    return fields, int(iters), err       # the solve's one host sync


def warm(entry, traffic):
    """One whole solve: the solver is compiled (or fetched) and run once."""
    _, iters, _ = _solve(entry, entry.state)
    return {"warm_iters": iters}


def window(entry, traffic, seconds):
    init, iters, last = entry.state, [], None
    t0 = time.perf_counter()
    while True:
        last = _solve(entry, init)
        iters.append(last[1])
        elapsed = time.perf_counter() - t0
        if elapsed >= seconds:
            break
    entry.state = None
    fields, _, err = last
    return {"state": fields, "window_s": elapsed, "steps": sum(iters),
            "solves": len(iters), "iters": iters, "err": float(err),
            "attempted": len(iters)}


def check(plain, traffic, warm, res, outputs, region):
    """The numbers compared: the last solve's fields and check value, and
    the step at which every solve stopped, against the reference's solve."""
    from yardstick import compare

    state, iters, err = plain.solve(traffic)
    out = compare.state_numbers(plain.ref, outputs, state, region)
    out["check_rel_err"] = abs(res["err"] - err) / err
    out["iters_gap"] = float(max(abs(i - iters) for i in res["iters"]))
    return out
