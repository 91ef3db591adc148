"""Runtime telemetry for the stencil engine (metrics, spans, compile counts).

Off by default and a true no-op when off: every instrumentation site in
the engine calls through the module-level singleton returned by
:func:`get`, which is the shared :data:`~repro.telemetry.collector.NULL`
object unless telemetry was enabled. Enabling:

* environment — ``REPRO_TELEMETRY=1`` (JSONL lands under
  ``$REPRO_TELEMETRY_DIR`` or ``./telemetry/``) or
  ``REPRO_TELEMETRY=/path/run.jsonl`` (explicit log path);
* code — ``telemetry.configure(path=...)``, or per-call via the
  ``telemetry=`` kwarg on ``solve_until`` (a ``Collector``, ``True``,
  ``False``, or ``None`` = inherit the global singleton).

The device program never changes: metrics derived from device values are
harvested only at host sync points that already exist (chunk/checkpoint
boundaries, final results) — see the package's test for the jaxpr proof.
"""
from __future__ import annotations

import atexit
import os
from typing import Optional, Union

from .collector import NULL, Collector, NullCollector, SCHEMA_VERSION

__all__ = [
    "Collector", "NullCollector", "NULL", "SCHEMA_VERSION",
    "get", "enabled", "configure", "configure_rank", "resolve", "reset",
    "count", "gauge", "observe", "event", "span",
]

_ACTIVE: Union[Collector, NullCollector, None] = None   # None = env not read yet


def _truthy(val: str) -> bool:
    return val.strip().lower() not in ("", "0", "false", "off", "no")


def _from_env() -> Union[Collector, NullCollector]:
    val = os.environ.get("REPRO_TELEMETRY", "")
    if not _truthy(val):
        return NULL
    if "/" in val or val.endswith(".jsonl"):
        path = val
    else:
        d = os.environ.get("REPRO_TELEMETRY_DIR", "telemetry")
        path = os.path.join(d, f"run_{os.getpid()}.jsonl")
    col = Collector(path, meta=_run_meta())
    atexit.register(col.close)
    return col


def _run_meta() -> dict:
    import sys

    meta = {"argv": sys.argv[:4]}
    try:
        import jax

        meta["backend"] = jax.default_backend()
    except Exception:
        pass
    return meta


def get() -> Union[Collector, NullCollector]:
    """The process-wide collector (the no-op singleton when disabled)."""
    global _ACTIVE
    if _ACTIVE is None:
        _ACTIVE = _from_env()
    return _ACTIVE


def enabled() -> bool:
    return get().enabled


def configure(path: Optional[str] = None, *, enabled: bool = True,
              meta: Optional[dict] = None,
              rank: Optional[int] = None) -> Union[Collector, NullCollector]:
    """Install (or disable) the global collector programmatically,
    overriding the environment. Returns the new active collector."""
    global _ACTIVE
    if _ACTIVE is not None and _ACTIVE.enabled:
        _ACTIVE.close()
    if not enabled:
        _ACTIVE = NULL
    else:
        _ACTIVE = Collector(path, meta={**_run_meta(), **(meta or {})},
                            rank=rank)
    return _ACTIVE


def configure_rank(rank: int,
                   path: Optional[str] = None) -> Union[Collector,
                                                        NullCollector]:
    """Per-rank stream for multi-process runs: when telemetry is enabled
    via the environment (or an explicit ``path`` is given), re-point the
    collector at ``rank_<rank>.jsonl`` beside the env-configured sink,
    with every record rank-stamped — the layout
    ``telemetry.report --merge 'rank_*.jsonl'`` interleaves. A no-op
    returning :data:`NULL` when telemetry is off (workers can call this
    unconditionally after rendezvous)."""
    if path is None:
        val = os.environ.get("REPRO_TELEMETRY", "")
        if not _truthy(val):
            return NULL
        if "/" in val or val.endswith(".jsonl"):
            d = os.path.dirname(val) or "."
        else:
            d = os.environ.get("REPRO_TELEMETRY_DIR", "telemetry")
        path = os.path.join(d, f"rank_{rank}.jsonl")
    return configure(path, meta={"rank": rank}, rank=rank)


def reset():
    """Forget any configured/env-resolved collector (tests)."""
    global _ACTIVE
    if _ACTIVE is not None and _ACTIVE.enabled:
        _ACTIVE.close()
    _ACTIVE = None


def resolve(telemetry) -> Union[Collector, NullCollector]:
    """Map a ``telemetry=`` kwarg to a collector: ``None`` inherits the
    global singleton, ``False`` forces the no-op, ``True`` forces an
    enabled collector (the global one if already enabled, else a fresh
    in-memory one), and a ``Collector`` is used as-is."""
    if telemetry is None:
        return get()
    if telemetry is False:
        return NULL
    if telemetry is True:
        g = get()
        return g if g.enabled else configure(None)
    return telemetry


def count(name, value=1, **labels):
    get().count(name, value, **labels)


def gauge(name, value, **labels):
    get().gauge(name, value, **labels)


def observe(name, value, **labels):
    get().observe(name, value, **labels)


def event(name, **attrs):
    get().event(name, **attrs)


def span(name, **attrs):
    return get().span(name, **attrs)
