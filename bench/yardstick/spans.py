"""The program's own spans and compile counts, read beside the device trace.

While its telemetry collector is on (``repro.telemetry``), the program
opens each span as a ``jax.profiler.TraceAnnotation``: a profiler session
records it on the host line of the thread that ran it, on the clock of
the device planes, under the span's name (``stencil.dispatch`` around an
eager ``@parallel`` call, ``stencil.scalars`` inside it, ``solve_until``,
``solve.dispatch``, ``elastic.chunk``). The collector also counts every
compile by phase (``jit.compile_s``, labelled ``fn`` and ``phase``).

These are the reductions from those to per-layer numbers; ``data`` is
``trace.load``'s dict of a window recorded by ``trace.recording``,
``records`` the collector's records. Each returns None where there is
nothing to read (the collector was off, or no such span ran in the
window).
"""
from __future__ import annotations

import statistics

from . import trace

DISPATCH = "stencil.dispatch"


def _window_line(data):
    """The window and the spans of the host line that holds it (the
    thread that ran the window), each ``(start, end, name)``."""
    for pl in data["planes"]:
        if trace.DEVICE.match(pl["name"]):
            continue
        for ln in pl["lines"]:
            for name, s, d, _ in ln["events"]:
                if name == trace.WINDOW:
                    spans = [(s0, s0 + d0, n) for n, s0, d0, _ in ln["events"]
                             if n != trace.WINDOW]
                    return (s, s + d), spans
    return None, []


def _chip0_busy(data, win):
    """Merged intervals in which an operation ran on the lowest-numbered
    chip, inside the window."""
    chips = sorted((int(m.group(1)), pl) for pl in data["planes"]
                   if (m := trace.DEVICE.match(pl["name"])))
    if not chips:
        return None
    ops = [(s, s + d) for ln in chips[0][1]["lines"]
           if ln["name"] == trace.OPS_LINE for _, s, d, _ in ln["events"]
           if s >= win[0] and s + d <= win[1]]
    return trace._union(ops)


def dispatch_ms(data, name: str = DISPATCH):
    """Median duration of the window thread's ``name`` spans that lie in
    the window, in milliseconds."""
    win, spans = _window_line(data)
    durs = [e - s for s, e, n in spans
            if n == name and win[0] <= s and e <= win[1]] if win else []
    return statistics.median(durs) * 1e-6 if durs else None


def idle_share_under(data, name: str = DISPATCH):
    """Percent of the window in which chip 0 ran no operation while the
    window's thread was inside a ``name`` span."""
    win, spans = _window_line(data)
    if win is None or win[1] <= win[0]:
        return None
    inside = trace._union([(max(s, win[0]), min(e, win[1]))
                           for s, e, n in spans
                           if n == name and e > win[0] and s < win[1]])
    busy = _chip0_busy(data, win)
    if not inside or busy is None:
        return None
    return 100.0 * trace._minus(inside, busy) / (win[1] - win[0])


def program_gaps(data, names):
    """Chip 0's idle time in the window by what the host was doing: each
    gap takes the name of the innermost program span (one of ``names``)
    that covers its middle, on any host thread, and where none does the
    name ``trace`` gives it (the shortest covering host span):
    ``[[label, seconds]]``, most idle first."""
    win, _ = _window_line(data)
    busy = _chip0_busy(data, win) if win else None
    if busy is None:
        return None
    edges = [win[0]] + [x for iv in busy for x in iv] + [win[1]]
    gaps = [(edges[j], edges[j + 1]) for j in range(0, len(edges) - 1, 2)
            if edges[j + 1] > edges[j]]
    mids = sorted(0.5 * (s + e) for s, e in gaps)
    width = {0.5 * (s + e): e - s for s, e in gaps}
    lines = trace._host_lines(data)
    names = set(names)
    program = [[sp for sp in ln if sp[2] in names] for ln in lines]

    def shortest(lines_):
        best = [None] * len(mids)
        for spans in lines_:
            for i, sp in enumerate(trace._innermost(mids, spans)):
                if sp is not None and (best[i] is None or
                                       sp[1] - sp[0] < best[i][1] - best[i][0]):
                    best[i] = sp
        return best

    by: dict = {}
    for m, sp, any_sp in zip(mids, shortest(program), shortest(lines)):
        label = (sp or any_sp or (0, 0, "no host span"))[2]
        by[label] = by.get(label, 0.0) + width[m]
    return sorted(([k, v * 1e-9] for k, v in by.items()),
                  key=lambda kv: -kv[1])


def setup_compile_s(records, until_ts: float):
    """Seconds of set-up, up to ``until_ts`` (wall clock), in which the
    program was compiling: the union of its ``jit.compile_s`` intervals,
    every phase (trace, lower, compile, cache fetch). Each record is
    emitted as its phase ends, so it spans ``[ts - value, ts]``; a trace
    nested in another (a jitted ``jnp`` function traced inside the
    program) counts once."""
    iv = [(r["ts"] - r["value"], r["ts"]) for r in records
          if r.get("kind") == "counter" and r.get("name") == "jit.compile_s"
          and r["ts"] <= until_ts]
    return trace._length(trace._union(iv)) if iv else None
