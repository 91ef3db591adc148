"""Seconds from the process's start to the first timed step: start-up,
the state built from the seed, compile (or cache fetch) and the warm
chunk or solve."""


def read(ctx):
    return ctx.run["setup_s"]
