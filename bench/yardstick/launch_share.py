"""One named launch's share of the HBM roofline, from the traced window:
the launch's device time is its total in the trace's breakdown by
operation name (``trace.reduce``'s ``device_ops``, the ten longest), its
count the iterations the window ran (one launch per iteration), its bytes
the configuration's reference's ``launch_bytes`` (operands and results
each once, as ``trace.launch_bytes`` counts them)."""
from __future__ import annotations


def read(ctx, launch: str):
    """Percent of the HBM roofline ``launch`` reached; None where the
    trace's breakdown does not name it or no iteration ran."""
    import harness

    from .peaks import peak

    if ctx.trace is None:
        return None
    seconds = dict(ctx.trace.breakdown["device_ops"]).get(launch)
    steps = ctx.run.get("steps")
    if not seconds or not steps:
        return None
    ref = harness.load_module("references", ctx.cfg["reference"])
    nbytes = ref.launch_bytes(ctx.cfg)[launch]
    bw = peak(ctx.kind)["hbm_bytes_per_s"]
    return 100.0 * steps * nbytes / (bw * seconds)
