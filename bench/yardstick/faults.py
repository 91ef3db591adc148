"""Faults planted in a run's timed path, to read what the comparison
gives when the program is off by one step or hands its time levels back
swapped. The benchmark's own runs never plant one; ``bench/readings.py
--fault`` reads them on the chip and ``bench/tests/test_faults.py`` shows
them caught."""
from __future__ import annotations


def extra_step(entry, ctx) -> None:
    """The answer is one step late: the final state takes one more step
    (the reference's own) before it is compared."""
    if getattr(entry, "interior_only", False):
        raise ValueError("extra_step needs the state in the reference's "
                         "layout")
    out = entry.outputs
    entry.outputs = lambda s: out(ctx.ref.step(s, ctx.p))


def swapped(entry, ctx) -> None:
    """The newest and the previous time level are handed back swapped."""
    if not ctx.ref.INCREMENTS:
        raise ValueError("the solver keeps one time level")
    out = entry.outputs

    def swap(s):
        o = dict(out(s))
        for new, old in ctx.ref.INCREMENTS:
            o[new], o[old] = o[old], o[new]
        return o

    entry.outputs = swap


FAULTS = {"extra_step": extra_step, "swapped": swapped}


def plant(name: str, entry, ctx) -> None:
    FAULTS[name](entry, ctx)
