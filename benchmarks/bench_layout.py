"""Paper C5: declarative allocation lets the framework pick the data layout
(SoA vs AoS) — this benchmark quantifies why that choice must exist.

Workload: 3-component vector diffusion (each component a 7-point stencil),
allocated either as SoA (3 contiguous arrays — unit-stride inner axis) or
AoS (one array with trailing component axis — stride-3 inner access).
On TPU the SoA layout keeps the 128-lane minor dimension dense; on CPU it
keeps vector loads unit-stride. The FieldSet allocator defaults to SoA and
exposes AoS per field (fields.py).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import Grid, FieldSet, fd3d as fd, teff


def _step_soa(comps, dt):
    return tuple(
        c.at[1:-1, 1:-1, 1:-1].add(dt * (fd.d2_xi(c) + fd.d2_yi(c) + fd.d2_zi(c)))
        for c in comps)


def _step_aos(arr, dt):
    def lap(c):
        return fd.d2_xi(c) + fd.d2_yi(c) + fd.d2_zi(c)
    upd = jnp.stack([lap(arr[..., i]) for i in range(arr.shape[-1])], axis=-1)
    return arr.at[1:-1, 1:-1, 1:-1, :].add(dt * upd)


def bench(n: int = 96, iters: int = 10, nsteps: int = 2):
    g = Grid((n,) * 3)
    fs = FieldSet(g)
    v_soa = fs.vector(3, init=1.0, layout="soa")
    v_aos = v_soa.as_aos()
    dt = 1e-4

    soa = jax.jit(lambda cs: _step_soa(cs, dt))
    aos = jax.jit(lambda a: _step_aos(a, dt))
    m_soa = teff.measure(lambda: soa(v_soa.components), iters=iters)
    m_aos = teff.measure(lambda: aos(v_aos.components), iters=iters)

    # temporally-blocked variants: k unrolled sweeps in one jit'd launch,
    # scored against the per-launch ideal traffic (a_eff / k)
    def _multi(step1):
        def run(x):
            for _ in range(nsteps):
                x = step1(x, dt)
            return x
        return jax.jit(run)

    soa_k = _multi(_step_soa)
    aos_k = _multi(_step_aos)
    m_soa_k = teff.measure(lambda: soa_k(v_soa.components), iters=iters)
    m_aos_k = teff.measure(lambda: aos_k(v_aos.components), iters=iters)

    a_eff = teff.a_eff(g.n_points, n_read=3, n_write=3, itemsize=4)
    a_blk = teff.a_eff_blocked(g.n_points, n_read=3, n_write=3, itemsize=4,
                               nsteps=nsteps)
    host_bw = teff.measure_host_bandwidth()
    return {
        "nsteps": nsteps,
        "soa_us": m_soa.median_s * 1e6,
        "aos_us": m_aos.median_s * 1e6,
        "soa_teff_GBs": m_soa.t_eff(a_eff) / 1e9,
        "aos_teff_GBs": m_aos.t_eff(a_eff) / 1e9,
        "soa_frac_of_host_peak": m_soa.t_eff(a_eff) / host_bw,
        "aos_frac_of_host_peak": m_aos.t_eff(a_eff) / host_bw,
        "soa_frac_of_host_peak_blocked":
            (a_blk / (m_soa_k.median_s / nsteps)) / host_bw,
        "aos_frac_of_host_peak_blocked":
            (a_blk / (m_aos_k.median_s / nsteps)) / host_bw,
        "soa_over_aos": m_aos.median_s / m_soa.median_s,
    }


def main():
    r = bench()
    print(f"layout_soa,{r['soa_us']:.1f},T_eff={r['soa_teff_GBs']:.2f}GB/s "
          f"frac={r['soa_frac_of_host_peak']:.3f} "
          f"frac_blocked_k{r['nsteps']}={r['soa_frac_of_host_peak_blocked']:.3f}")
    print(f"layout_aos,{r['aos_us']:.1f},T_eff={r['aos_teff_GBs']:.2f}GB/s "
          f"frac={r['aos_frac_of_host_peak']:.3f} "
          f"frac_blocked_k{r['nsteps']}={r['aos_frac_of_host_peak_blocked']:.3f}")
    print(f"layout_soa_speedup,{r['soa_over_aos']:.2f},x")
    return r


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
