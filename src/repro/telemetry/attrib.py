"""Roofline-gap attribution: measured per-step seconds vs the IR cost model.

The paper's argument is T_eff against the bandwidth roofline; a production
solve should therefore report, per kernel launch configuration,

    t_eff_measured  = A_eff / t_step_measured            (bytes/s)
    t_eff_model     = A_eff / t_step_model               (bytes/s)
    roofline_fraction = t_eff_measured / t_eff_model
                      = t_step_model / t_step_measured

where ``t_step_model`` comes from ``StencilCostModel.predict_per_step_s``
(max of the memory and compute roofline terms for the launch's actual
tile / temporal-blocking depth / march axis / check cadence). A fraction
near 1.0 means the launch runs at its modeled roofline; 0.58 means "this
kernel leaves 42% of its modeled throughput on the table" — a first-class
metric instead of an offline bench artifact.

The hardware spec is the accelerator's published peaks, looked up by
``device_kind`` in ``teff.DEVICE_PEAKS`` (an unknown kind is an error),
or on CPU a cached STREAM-copy measurement; it can be pinned with
``REPRO_TELEMETRY_BW_GBS`` / ``REPRO_TELEMETRY_FLOPS_G`` so CI numbers
don't depend on a noisy runner measurement.
"""
from __future__ import annotations

import os
from typing import Optional

__all__ = ["default_hardware", "attribute", "reset_hardware_cache"]

_HW_CACHE: list = []      # [HardwareSpec] once resolved


def reset_hardware_cache():
    _HW_CACHE.clear()


def default_hardware():
    """The roofline peak for the current process (cached after first use)."""
    if _HW_CACHE:
        return _HW_CACHE[0]
    from ..core import teff

    bw_env = os.environ.get("REPRO_TELEMETRY_BW_GBS")
    fl_env = os.environ.get("REPRO_TELEMETRY_FLOPS_G")
    if bw_env:
        bw = float(bw_env) * 1e9
        # CPU-ish ridge point unless pinned: ~8 flop/byte
        flops = float(fl_env) * 1e9 if fl_env else 8.0 * bw
        hw = teff.HardwareSpec("pinned", peak_bw=bw, peak_flops=flops)
    else:
        import jax

        dev = jax.devices()[0]
        if dev.platform != "cpu":
            hw = teff.device_peaks(dev.device_kind)
        else:
            bw = teff.measure_host_bandwidth()
            flops = float(fl_env) * 1e9 if fl_env else 8.0 * bw
            hw = teff.HardwareSpec("host-cpu (STREAM-measured)",
                                   peak_bw=bw, peak_flops=flops)
    _HW_CACHE.append(hw)
    return hw


def attribute(col, kernel_name: str, per_step_s: float, cost, *,
              nsteps: int = 1, tile=None, march_axis: Optional[int] = None,
              check_every: Optional[int] = None, fused_checks: bool = True,
              hw=None) -> dict:
    """Emit the roofline-gap record for one measured launch config.

    ``cost`` is a :class:`~repro.ir.cost.StencilCostModel`; ``tile``
    defaults to the whole grid (the jnp backend's effective tile).
    Emits gauges ``roofline.t_eff_measured_GBs`` / ``..._model_GBs`` /
    ``roofline.fraction`` labeled by kernel, plus one ``roofline`` event
    carrying the full context; returns the computed dict."""
    if per_step_s <= 0:
        return {}
    hw = hw or default_hardware()
    tile = tuple(tile) if tile is not None else tuple(cost.shape)
    a = cost.a_eff_bytes(nsteps)
    t_model = cost.predict_per_step_s(tile, nsteps, hw,
                                      march_axis=march_axis,
                                      check_every=check_every,
                                      fused_checks=fused_checks)
    t_eff_measured = a / per_step_s
    t_eff_model = a / t_model if t_model > 0 else float("inf")
    frac = t_model / per_step_s
    out = {"kernel": kernel_name, "per_step_s": per_step_s,
           "model_per_step_s": t_model, "a_eff_bytes": a,
           "t_eff_measured": t_eff_measured, "t_eff_model": t_eff_model,
           "roofline_fraction": frac, "hw": hw.name,
           "peak_bw_GBs": hw.peak_bw / 1e9, "tile": tile, "nsteps": nsteps,
           "march_axis": march_axis, "check_every": check_every}
    col.gauge("roofline.t_eff_measured_GBs", t_eff_measured / 1e9,
              kernel=kernel_name)
    col.gauge("roofline.t_eff_model_GBs", t_eff_model / 1e9,
              kernel=kernel_name)
    col.gauge("roofline.fraction", frac, kernel=kernel_name)
    col.event("roofline", **out)
    return out
