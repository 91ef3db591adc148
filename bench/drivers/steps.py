"""Fixed-step traffic: chunks of ``chunk_steps`` steps, each chunk ending in
``block_until_ready``, for as long as the window lasts. The window closes
at the end of the first chunk that finishes after ``seconds``. The
reference replays every step the program made, warm chunk included."""
from __future__ import annotations

import time

import jax


def _chunk(entry, state, steps):
    for _ in range(steps // entry.steps_per_call):
        state = entry.advance(state)
    return jax.block_until_ready(state)


def warm(entry, traffic):
    """One whole chunk: every program the window runs is compiled (or
    fetched from the cache) and run once."""
    steps = _steps(entry, traffic)
    entry.state = _chunk(entry, entry.state, steps)
    return {"warm_steps": steps}


def window(entry, traffic, seconds):
    steps = _steps(entry, traffic)
    state, done, chunks = entry.state, 0, 0
    entry.state = None
    t0 = time.perf_counter()
    while True:
        state = _chunk(entry, state, steps)
        done += steps
        chunks += 1
        elapsed = time.perf_counter() - t0
        if elapsed >= seconds:
            break
    return {"state": state, "window_s": elapsed, "steps": done,
            "chunks": chunks, "attempted": chunks}


def check(plain, traffic, warm, res, outputs, region):
    """The numbers compared: the program's fields after all its steps
    against the reference's after as many."""
    from yardstick import compare

    state = plain.replay(warm["warm_steps"] + res["steps"])
    return compare.state_numbers(plain.ref, outputs, state, region)


def _steps(entry, traffic):
    steps = int(traffic["chunk_steps"])
    if steps % entry.steps_per_call:
        raise ValueError(f"chunk_steps {steps} is not a multiple of the "
                         f"entry's {entry.steps_per_call} steps per call")
    return steps
