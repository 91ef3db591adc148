"""T_eff — the paper's effective-memory-throughput performance model (C7).

    T_eff = A_eff / t,     A_eff = n_IO * n_gridpoints * sizeof(eltype)

where ``n_IO`` counts the arrays that *must* be read or written once per
time step under perfect reuse (for the 3-D diffusion solver of Fig. 1:
read T and Ci, write T2 -> n_IO = 3; the paper's canonical definition in
Räss et al. 2022 [5] uses reads+writes of fields that change every step,
i.e. A_eff = (2 * n_rw + n_r) * V; we expose both and use the explicit
read/write counts everywhere).

The fraction T_eff / T_peak is the memory-roofline fraction this repo
reports as its §Perf score (the paper reaches 0.88 on P100 / 0.93 on A100).
"""
from __future__ import annotations

import dataclasses
import math
import time
from typing import Callable, Sequence

import jax
import numpy as np


@dataclasses.dataclass(frozen=True)
class HardwareSpec:
    name: str
    peak_bw: float  # bytes/s, HBM/DRAM
    peak_flops: float  # FLOP/s at the relevant precision
    link_bw: float = 0.0  # bytes/s per ICI/NVLink link (for collective roofline)
    hbm_bytes: float = 0.0

    @property
    def ridge_intensity(self) -> float:
        return self.peak_flops / self.peak_bw


# Hardware constants. TPU numbers are the task-specified v5e targets; the
# GPU entries reproduce the paper's Fig. 2 reference hardware.
TPU_V5E = HardwareSpec("TPU v5e", peak_bw=819e9, peak_flops=197e12, link_bw=50e9,
                       hbm_bytes=16e9)
A100_SXM4 = HardwareSpec("NVIDIA A100 SXM4", peak_bw=1355e9, peak_flops=312e12,
                         link_bw=300e9, hbm_bytes=40e9)
P100_PCIE = HardwareSpec("NVIDIA P100 PCIe", peak_bw=561e9, peak_flops=18.7e12,
                         link_bw=16e9, hbm_bytes=16e9)

# Published peaks keyed by ``jax.Device.device_kind``. TPU v5e (JAX
# reports "TPU v5 lite"): Google Cloud documentation, "TPU v5e" — 197
# TFLOP/s bf16, 16 GB of HBM at 819 GB/s. GPUs: the vendors' A100/P100
# datasheets (the paper's Fig. 2 hardware).
DEVICE_PEAKS = {
    "TPU v5 lite": TPU_V5E,
    "TPU v5e": TPU_V5E,
    "NVIDIA A100-SXM4-40GB": A100_SXM4,
    "Tesla P100-PCIE-16GB": P100_PCIE,
}


def device_peaks(device_kind: str) -> HardwareSpec:
    """The published peaks of one accelerator kind; a kind not in
    :data:`DEVICE_PEAKS` is an error, never a default."""
    try:
        return DEVICE_PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks recorded for device kind {device_kind!r} "
            f"(known: {sorted(DEVICE_PEAKS)}) — add its entry, with its "
            "source, to repro.core.teff.DEVICE_PEAKS") from None


def a_eff(n_points: int, n_read: int, n_write: int, itemsize: int) -> int:
    """Effective bytes moved per step: each counted field crosses HBM once."""
    return (n_read + n_write) * n_points * itemsize


def a_eff_blocked(n_points: int, n_read: int, n_write: int, itemsize: int,
                  nsteps: int = 1) -> float:
    """Ideal per-step HBM traffic under k-step temporal blocking.

    A k-fused launch moves each counted field across HBM once per *k* steps,
    so the per-step effective volume divides by k. T_eff computed against
    this volume can exceed the single-sweep memory roofline — that is the
    point of temporal blocking. ``nsteps=1`` degenerates to :func:`a_eff`.
    """
    return a_eff(n_points, n_read, n_write, itemsize) / max(int(nsteps), 1)


def window_overlap_factor(block, halo, nsteps: int = 1,
                          march_axis: int | None = None) -> float:
    """Read-amplification of a tiled launch vs ideal once-per-sweep
    streaming: ``prod_a (b_a + k*(lo_a + hi_a)) / b_a`` over the axes
    whose windows overlap. The all-parallel launch refetches along every
    axis; a streamed launch (``march_axis``) carries its march-axis halo
    planes in on-chip scratch, so that axis drops out of the product —
    which is exactly the traffic the marching mode saves."""
    k = max(int(nsteps), 1)
    block = tuple(int(b) for b in block)
    if isinstance(halo, int):
        halo = ((halo, halo),) * len(block)
    f = 1.0
    for a, (b, (lo, hi)) in enumerate(zip(block, halo)):
        if march_axis is not None and a == march_axis:
            continue
        f *= (b + k * (lo + hi)) / b
    return f


def a_eff_streamed(n_points: int, n_read: int, n_write: int, itemsize: int,
                   nsteps: int = 1, overlap: float = 1.0) -> float:
    """Per-step HBM traffic of a streamed (marching) launch: each read
    field is fetched ~once per sweep times the residual window-overlap
    factor of the non-marching axes (``window_overlap_factor`` with the
    march axis excluded; 1.0 = perfect reuse), writes stream out once,
    and a k-fused launch amortizes both over k steps. The refetched
    all-parallel traffic is the same formula with the full overlap
    factor — the difference is what ``march_axis=`` eliminates."""
    return ((n_read * overlap + n_write) * n_points * itemsize
            / max(int(nsteps), 1))


def halo_compute_overhead(block, radius: int, nsteps: int) -> float:
    """Fraction of *redundant* gridpoint-updates a k-fused launch performs
    relative to k ideal sweeps over the block.

    Sweep s of a temporally-blocked kernel updates the block extended by
    ``(k-1-s)*radius`` cells per side (the shrinking halo cone), so blocks
    recompute their neighbors' edge cells. When this ratio grows faster
    than the k-fold A_eff saving, larger k stops paying off — that is the
    classic temporal-blocking trade-off (redundant work vs traffic).
    """
    k = max(int(nsteps), 1)
    block = tuple(int(b) for b in block)
    ideal = k * math.prod(block)
    total = sum(
        math.prod(b + 2 * (k - 1 - s) * radius for b in block) for s in range(k)
    )
    return total / ideal - 1.0


def a_eff_checked(a_eff_step: float, check_bytes: float,
                  check_every: int = 1, fused: bool = True) -> float:
    """Per-step ideal HBM traffic of an iterative solver that checks
    convergence every ``check_every`` steps.

    ``fused=True`` is the in-launch reduction epilogue: the check folds
    over data already in flight, so the only extra traffic is the
    per-tile partials write (rounded to zero here — O(n_blocks) scalars).
    ``fused=False`` is the separate norm pass: ``check_bytes`` (each
    operand field re-read once — e.g. ``ir.check_io_bytes``) lands on
    every check step and is amortized over the cadence. Keeping both in
    the T_eff table is what makes check traffic visible instead of
    silently inflating the "compute" time of check steps."""
    m = max(int(check_every), 1)
    extra = 0.0 if fused else check_bytes / m
    return a_eff_step + extra


def io_counts_from_ir(ir) -> tuple[int, int]:
    """(n_read, n_write) derived from a traced ``repro.ir.StencilIR``
    instead of hand-counting which fields cross HBM — the IR knows which
    arguments the update actually reads."""
    return ir.io_counts()


def a_eff_from_ir(ir, itemsize: int, nsteps: int = 1,
                  field_itemsizes=None) -> float:
    """A_eff derived from the stencil IR: exact per-field byte volumes
    (staggered fields at their own extents; mixed-precision fields at
    their own storage width via ``field_itemsizes``, a ``{field:
    itemsize}`` mapping), divided by the temporal-blocking depth.
    Replaces hand-supplied ``n_read``/``n_write`` for any kernel built
    through ``@parallel``."""
    return (ir.io_bytes(itemsize, field_itemsizes=field_itemsizes)
            / max(int(nsteps), 1))


def t_eff(a_eff_bytes: float, seconds: float) -> float:
    """Effective throughput in bytes/s."""
    return a_eff_bytes / seconds


def fraction(throughput: float, hw: HardwareSpec) -> float:
    return throughput / hw.peak_bw


@dataclasses.dataclass
class Measurement:
    median_s: float
    ci95_s: tuple[float, float]
    samples_s: list[float]

    def t_eff(self, a_eff_bytes: float) -> float:
        return t_eff(a_eff_bytes, self.median_s)

    # Jitter percentiles over the raw per-iteration samples: the median
    # alone hides straggling iterations (GC pauses, a noisy neighbor, a
    # slow link), which is exactly what a perf trajectory wants to catch.
    @property
    def mean_s(self) -> float:
        return float(np.mean(self.samples_s))

    @property
    def p50_s(self) -> float:
        return float(np.percentile(self.samples_s, 50))

    @property
    def p90_s(self) -> float:
        return float(np.percentile(self.samples_s, 90))

    @property
    def max_s(self) -> float:
        return float(max(self.samples_s))

    def percentiles(self) -> dict[str, float]:
        """{"mean_s", "p50_s", "p90_s", "max_s"} — the jitter summary
        bench rows embed next to the median."""
        return {"mean_s": self.mean_s, "p50_s": self.p50_s,
                "p90_s": self.p90_s, "max_s": self.max_s}


def measure(fn: Callable[[], object], iters: int = 20, warmup: int = 3,
            inner: int = 1) -> Measurement:
    """Median wall time with a bootstrap 95% CI (paper Fig. 2 methodology:
    medians of 20 samples with confidence interval). The returned
    :class:`Measurement` also exposes p50/p90/max per-iteration jitter
    percentiles over the raw samples."""
    for _ in range(warmup):
        jax.block_until_ready(fn())
    samples = []
    for _ in range(iters):
        t0 = time.perf_counter()
        for _ in range(inner):
            out = fn()
        jax.block_until_ready(out)
        samples.append((time.perf_counter() - t0) / inner)
    med = float(np.median(samples))
    rng = np.random.RandomState(0)
    boots = [float(np.median(rng.choice(samples, size=len(samples)))) for _ in range(200)]
    lo, hi = float(np.percentile(boots, 2.5)), float(np.percentile(boots, 97.5))
    return Measurement(med, (lo, hi), samples)


def measure_host_bandwidth(nbytes: int = 1 << 28) -> float:
    """Rough STREAM-copy estimate of this host's achievable memory bandwidth,
    used as T_peak for the CPU rows of the Fig. 2 reproduction."""
    a = np.ones(nbytes // 8, dtype=np.float64)
    b = np.empty_like(a)
    t0 = time.perf_counter()
    reps = 3
    for _ in range(reps):
        np.copyto(b, a)
    dt = (time.perf_counter() - t0) / reps
    return 2 * a.nbytes / dt  # read + write
