"""The reduction from a profiler trace of the window to per-layer numbers.

``recording`` wraps the window in JAX's profiler (the Python tracer
off) and a ``bench.window`` annotation on the host. ``load`` turns the
``.xplane.pb`` file into plain lists; ``reduce`` turns those into the
numbers the metric readers take. The rules that match events are here
and nowhere else, and ``tests/test_trace.py`` checks them on a small
trace recorded on the chip.

Matching rules (for the events JAX 0.9 writes for a TPU v5e):

* a device is a plane named ``/device:TPU:<n>``; its operations are the
  events of its ``XLA Ops`` line, each named by its whole HLO instruction
  (``%name = shape opcode(operands), attributes``); a ``while`` event
  holds its body's events, so an operation's own time is its duration
  less that of the events inside it;
* the window is the host's ``bench.window`` annotation;
* a stencil launch is an operation whose HLO is a ``custom-call`` with
  ``custom_call_target="tpu_custom_call"`` (every Pallas kernel the
  engine builds; the instruction's name is not stable: ``tpu_custom_call``
  for an eager call, the kernel body's or the jitted function's name
  inside a jit);
* a collective is an operation whose opcode is one of ``COLLECTIVES``
  (with ``-start``/``-done`` for the asynchronous forms), and every event
  of the ``Async XLA Ops`` line;
* the bytes of a launch are its operands and results, each once, from the
  shapes and element types in its HLO text.
"""
from __future__ import annotations

import contextlib
import glob
import os
import re
import types

WINDOW = "bench.window"
DEVICE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute", "send", "recv")
_BYTES = {"f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4, "s8": 1,
          "u8": 1, "pred": 1, "f64": 8, "s64": 8, "u64": 8, "s16": 2,
          "u16": 2}
_SHAPE = re.compile(r"\b(" + "|".join(_BYTES) + r")\[([0-9,]*)\]")


@contextlib.contextmanager
def recording(tdir: str):
    """Profile the block into ``tdir`` (the Python tracer off), inside the
    host annotation that marks the window."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(tdir, profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation(WINDOW):
            yield
    finally:
        jax.profiler.stop_trace()


def load(tdir: str, keep_stats=("hlo_op", "long_name", "hlo_category",
                                 "tf_op")) -> dict:
    """The trace as ``{"planes": [{"name", "lines": [{"name", "events":
    [[name, start_ns, dur_ns, {stat: value}]]}]}]}``, keeping device
    planes and the host lines that hold the window annotation."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(tdir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no trace under {tdir}")
    planes = []
    for pl in ProfileData.from_file(paths[-1]).planes:
        dev = DEVICE.match(pl.name) is not None
        if not dev and not pl.name.startswith("/host:"):
            continue
        lines = []
        for ln in pl.lines:
            evs = []
            for e in ln.events:
                stats = {}
                if dev:
                    for k, v in e.stats:
                        if k in keep_stats:
                            stats[k] = v if isinstance(v, (int, float)) \
                                else str(v)
                evs.append([e.name, float(e.start_ns), float(e.duration_ns),
                            stats])
            lines.append({"name": ln.name, "events": evs})
        planes.append({"name": pl.name, "lines": lines})
    return {"planes": planes}


_KERNEL = 'custom_call_target="tpu_custom_call"'
_OPCODE = re.compile(r"\b(" + "|".join(COLLECTIVES)
                     + r")(-start|-done)?\(")


def op_name(text: str) -> str:
    """The instruction's name without ``%`` and numeric suffix: ``while``,
    ``tpu_custom_call``, ``collective-permute-start``."""
    name = text.split(" = ", 1)[0].lstrip("%").strip()
    return re.sub(r"\.\d+$", "", name)


def _op_kind(name: str, stats: dict) -> str:
    """``kernel``, ``collective`` or ``other``, by the rules above."""
    text = str(stats.get("long_name", name))
    if _KERNEL in text:
        return "kernel"
    head = text.split(" = ", 1)[-1].split("),", 1)[0]
    if _OPCODE.search(head) or re.sub(r"-(start|done)$", "",
                                       op_name(text)) in COLLECTIVES:
        return "collective"
    return "other"


def launch_bytes(name: str, stats: dict):
    """Bytes of a launch's operands and results, each counted once, from
    the typed shapes in its HLO text; None where the text has none."""
    text = str(stats.get("long_name", name))
    text = text.split("custom_call_target")[0]
    shapes = _SHAPE.findall(text)
    if not shapes:
        return None
    total = 0
    for dtype, dims in shapes:
        n = 1
        for d in filter(None, dims.split(",")):
            n *= int(d)
        total += n * _BYTES[dtype]
    return total


def _union(intervals):
    """Merged, sorted intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _length(merged):
    return sum(e - s for s, e in merged)


def _minus(a, b):
    """Length of merged intervals ``a`` not covered by merged ``b``."""
    total, j = 0.0, 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                total += b[k][0] - cur
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            total += e - cur
    return total


def _window(data):
    for pl in data["planes"]:
        if DEVICE.match(pl["name"]):
            continue
        for ln in pl["lines"]:
            for name, s, d, _ in ln["events"]:
                if name == WINDOW:
                    return s, s + d
    return None


def _host_lines(data):
    """Host spans, one start-sorted list per thread (spans of one thread
    nest)."""
    lines = []
    for pl in data["planes"]:
        if DEVICE.match(pl["name"]):
            continue
        for ln in pl["lines"]:
            spans = sorted((s, s + d, name) for name, s, d, _ in ln["events"]
                           if name != WINDOW and d > 0)
            if spans:
                lines.append(spans)
    return lines


def _self_times(ops):
    """Each operation's duration less that of the operations inside it
    (a ``while`` holds its body's launches)."""
    order = sorted(range(len(ops)), key=lambda i: (ops[i][1], -ops[i][2]))
    own = [ev[2] for ev in ops]
    stack = []
    for i in order:
        s, e = ops[i][1], ops[i][1] + ops[i][2]
        while stack and ops[stack[-1]][1] + ops[stack[-1]][2] <= s:
            stack.pop()
        if stack and e <= ops[stack[-1]][1] + ops[stack[-1]][2]:
            own[stack[-1]] -= ops[i][2]
        stack.append(i)
    return own


def reduce(src, device_ids=None):
    """Per-layer numbers from a trace (a directory or ``load``'s dict)."""
    data = load(src) if isinstance(src, str) else src
    win = _window(data)

    def inside(evs):
        if win is None:
            return evs
        return [ev for ev in evs if ev[1] >= win[0]
                and ev[1] + ev[2] <= win[1]]

    chips = []
    for pl in sorted(data["planes"], key=lambda p: p["name"]):
        m = DEVICE.match(pl["name"])
        if not m or (device_ids is not None
                     and int(m.group(1)) not in device_ids):
            continue
        ops = inside([ev for ln in pl["lines"] if ln["name"] == OPS_LINE
                      for ev in ln["events"]])
        asyncs = inside([ev for ln in pl["lines"]
                         if ln["name"] == ASYNC_LINE for ev in ln["events"]])
        if ops:
            chips.append((ops, asyncs))
    if win is None and chips:
        evs = [ev for ops, _ in chips for ev in ops]
        win = (min(ev[1] for ev in evs), max(ev[1] + ev[2] for ev in evs))
    window_ns = (win[1] - win[0]) if win else 0.0
    busy, exposed, gaps = [], [], []
    k_ns, k_bytes, k_n, unknown_bytes = 0.0, 0.0, 0, 0
    totals: dict = {}
    for i, (ops, asyncs) in enumerate(chips):
        allm = _union([(s, s + d) for _, s, d, _ in ops])
        busy.append(_length(allm))
        kinds = [_op_kind(n, st) for n, _, _, st in ops]
        coll = _union([(s, s + d) for (_, s, d, _), k in zip(ops, kinds)
                       if k == "collective"]
                      + [(s, s + d) for _, s, d, _ in asyncs])
        comp = _union([(s, s + d) for (_, s, d, _), k in zip(ops, kinds)
                       if k != "collective"])
        exposed.append(_minus(coll, comp))
        for (name, s, d, st), k, own in zip(ops, kinds, _self_times(ops)):
            label = op_name(name)
            totals[label] = totals.get(label, 0.0) + own
            if k == "kernel":
                b = launch_bytes(name, st)
                if b is None:
                    unknown_bytes += 1
                else:
                    k_ns += d
                    k_bytes += b
                    k_n += 1
        if i == 0 and win is not None:
            edges = [win[0]] + [x for iv in allm for x in iv] + [win[1]]
            gaps = [(edges[j], edges[j + 1])
                    for j in range(0, len(edges) - 1, 2)
                    if edges[j + 1] > edges[j]]
    n = max(len(chips), 1)
    ops_top = sorted(totals.items(), key=lambda kv: -kv[1])[:10]
    return types.SimpleNamespace(
        chips=len(chips), window_s=window_ns * 1e-9,
        busy_s=(sum(busy) / n * 1e-9) if chips else None,
        exposed_collective_s=(sum(exposed) / n * 1e-9) if chips else None,
        kernel_s=k_ns * 1e-9, kernel_bytes=k_bytes, kernel_launches=k_n,
        launches_without_bytes=unknown_bytes,
        breakdown={
            "device_ops": [[k, v / n * 1e-9] for k, v in ops_top],
            "idle_gaps": _label_gaps(gaps, _host_lines(data))})


def _innermost(mids, spans):
    """For each of the sorted ``mids``, the innermost span of one thread
    that covers it, or None."""
    out, stack, j = [], [], 0
    for m in mids:
        while j < len(spans) and spans[j][0] <= m:
            while stack and stack[-1][1] < spans[j][0]:
                stack.pop()
            stack.append(spans[j])
            j += 1
        while stack and stack[-1][1] < m:
            stack.pop()
        out.append(stack[-1] if stack else None)
    return out


def _label_gaps(gaps, lines):
    """Idle time by what the host was doing: each gap takes the name of
    the shortest host span, on any thread, that covers its middle."""
    mids = sorted(0.5 * (s + e) for s, e in gaps)
    width = {0.5 * (s + e): e - s for s, e in gaps}
    best = [None] * len(mids)
    for spans in lines:
        for i, sp in enumerate(_innermost(mids, spans)):
            if sp is not None and (best[i] is None or
                                   sp[1] - sp[0] < best[i][1] - best[i][0]):
                best[i] = sp
    by = {}
    for m, sp in zip(mids, best):
        label = sp[2] if sp is not None else "no host span"
        by[label] = by.get(label, 0.0) + width[m]
    top = sorted(by.items(), key=lambda kv: -kv[1])[:10]
    return [[k, v * 1e-9] for k, v in top]


def hbm_share(red, device_kind):
    """Percent of the HBM roofline the stencil launches reached; None
    where no launch (or no launch's bytes) was found."""
    from .peaks import peak

    if not red.kernel_launches or red.launches_without_bytes:
        return None
    bw = peak(device_kind)["hbm_bytes_per_s"]
    return 100.0 * red.kernel_bytes / (bw * red.kernel_s)


def exposed_collective_share(red):
    if not red.window_s or red.exposed_collective_s is None:
        return None
    return 100.0 * red.exposed_collective_s / red.window_s
