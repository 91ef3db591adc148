"""The @parallel engine: backend equivalence, math-close vs explicit
notation (paper §3 E2), launch-parameter derivation."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import Grid, FieldSet, fd2d, fd3d, init_parallel_stencil
from repro.kernels import ref
from repro.kernels.stencil import (LaunchFootprintError, derive_launch,
                                   launch_footprint_bytes,
                                   preflight_vmem)


def _diffusion_kernels(fd):
    def math_close(T2, T, Ci, lam, dt, _dx, _dy, _dz):
        return {"T2": fd.inn(T) + dt * (lam * fd.inn(Ci) * (
            fd.d2_xi(T) * _dx ** 2 + fd.d2_yi(T) * _dy ** 2 +
            fd.d2_zi(T) * _dz ** 2))}

    def explicit(T2, T, Ci, lam, dt, _dx, _dy, _dz):
        c = T[1:-1, 1:-1, 1:-1]
        lap = ((T[2:, 1:-1, 1:-1] - 2 * c + T[:-2, 1:-1, 1:-1]) * _dx ** 2
               + (T[1:-1, 2:, 1:-1] - 2 * c + T[1:-1, :-2, 1:-1]) * _dy ** 2
               + (T[1:-1, 1:-1, 2:] - 2 * c + T[1:-1, 1:-1, :-2]) * _dz ** 2)
        return {"T2": c + dt * (lam * Ci[1:-1, 1:-1, 1:-1] * lap)}

    return math_close, explicit


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
@pytest.mark.parametrize("notation", ["math_close", "explicit"])
def test_backends_and_notations_match_oracle(backend, notation, rng):
    shape = (24, 16, 32)
    T = jnp.asarray(rng.rand(*shape), jnp.float32)
    Ci = jnp.asarray(rng.rand(*shape) + 0.5, jnp.float32)
    dt, lam = 1e-4, 1.0
    inv = tuple(float(s - 1) for s in shape)
    ps = init_parallel_stencil(backend=backend, ndims=3)
    mc, ex = _diffusion_kernels(fd3d)
    kern = ps.parallel(outputs=("T2",))(mc if notation == "math_close" else ex)
    got = kern(T2=T, T=T, Ci=Ci, lam=lam, dt=dt, _dx=inv[0], _dy=inv[1],
               _dz=inv[2])
    want = ref.diffusion3d_step(T, T, Ci, lam, dt, *inv)
    np.testing.assert_allclose(got, want, atol=1e-6, rtol=1e-6)


def test_2d_kernel_both_backends(rng):
    shape = (32, 48)
    U = jnp.asarray(rng.rand(*shape), jnp.float32)

    def kern(U2, U, dt):
        return {"U2": fd2d.inn(U) + dt * (fd2d.d2_xi(U) + fd2d.d2_yi(U))}

    outs = []
    for backend in ("jnp", "pallas"):
        ps = init_parallel_stencil(backend=backend, ndims=2)
        k = ps.parallel(outputs=("U2",))(kern)
        outs.append(np.asarray(k(U2=U, U=U, dt=1e-3)))
    np.testing.assert_allclose(outs[0], outs[1], atol=1e-6)


def test_multi_output_kernel(rng):
    shape = (16, 16, 16)
    A = jnp.asarray(rng.rand(*shape), jnp.float32)
    B = jnp.asarray(rng.rand(*shape), jnp.float32)

    def kern(A2, B2, A, B, dt):
        return {"A2": fd3d.inn(A) + dt * fd3d.inn(B),
                "B2": fd3d.inn(B) - dt * fd3d.inn(A)}

    for backend in ("jnp", "pallas"):
        ps = init_parallel_stencil(backend=backend, ndims=3)
        k = ps.parallel(outputs=("A2", "B2"))(kern)
        outs = k(A2=A, B2=B, A=A, B=B, dt=0.1)
        np.testing.assert_allclose(outs["A2"][1:-1, 1:-1, 1:-1],
                                   fd3d.inn(A) + 0.1 * fd3d.inn(B), atol=1e-6)
        np.testing.assert_allclose(outs["B2"][1:-1, 1:-1, 1:-1],
                                   fd3d.inn(B) - 0.1 * fd3d.inn(A), atol=1e-6)


def test_time_loop_equivalence(rng):
    """Several steps of the full solver: pallas == jnp == oracle."""
    g = Grid((16, 16, 16))
    fs = FieldSet(g)
    T0 = fs.from_fn(lambda x, y, z: jnp.exp(-((x - .5) ** 2 + (y - .5) ** 2 +
                                              (z - .5) ** 2) / 0.02))
    Ci = fs.ones() / 2.0
    lam = 1.0
    dt = g.stable_diffusion_dt(lam / 0.5)
    inv = g.inv_spacing
    mc, _ = _diffusion_kernels(fd3d)
    results = {}
    for backend in ("jnp", "pallas"):
        ps = init_parallel_stencil(backend=backend, ndims=3)
        k = ps.parallel(outputs=("T2",))(mc)
        T, T2 = T0, T0
        for _ in range(5):
            T2 = k(T2=T2, T=T, Ci=Ci, lam=lam, dt=dt, _dx=inv[0], _dy=inv[1],
                   _dz=inv[2])
            T, T2 = T2, T
        results[backend] = np.asarray(T)
    np.testing.assert_allclose(results["jnp"], results["pallas"], atol=2e-6)


def test_derive_launch_divides_and_fits():
    for shape in [(512, 512, 512), (96, 64, 384), (17, 34, 51)]:
        grid, block = derive_launch(shape, radius=1, n_fields=3, itemsize=4)
        assert all(s % b == 0 for s, b in zip(shape, block))
        window = 3 * np.prod([b + 2 for b in block]) * 4
        assert window <= 8 << 20
        assert all(g * b == s for g, b, s in zip(grid, block, shape))


def test_derive_launch_respects_tile_override():
    grid, block = derive_launch((64, 64, 64), 1, 3, 4, tile=(8, 8, 64))
    assert block == (8, 8, 64) and grid == (8, 8, 1)
    with pytest.raises(ValueError):
        derive_launch((64, 64, 64), 1, 3, 4, tile=(7, 8, 64))
    with pytest.raises(ValueError):  # rank mismatch
        derive_launch((64, 64, 64), 1, 3, 4, tile=(8, 64))


def test_derive_launch_vmem_budget_shrinks_blocks():
    """A tighter budget must shrink the halo-extended working set while the
    blocks keep dividing the array extents."""
    shape = (256, 256, 256)
    big = 8 << 20
    small = 1 << 20
    _, b_big = derive_launch(shape, 1, 3, 4, vmem_budget=big)
    _, b_small = derive_launch(shape, 1, 3, 4, vmem_budget=small)

    def window(blk, halo=1):
        return 3 * np.prod([b + 2 * halo for b in blk]) * 4

    assert window(b_small) <= small
    assert window(b_small) < window(b_big)
    assert all(s % b == 0 for s, b in zip(shape, b_small))


def test_preflight_rejects_oversized_explicit_tile():
    """An explicit tile whose halo-extended windows exceed device VMEM
    must fail at derivation time with a pointed admission error, not as
    an opaque backend allocation failure later."""
    shape = (512, 512, 512)
    with pytest.raises(LaunchFootprintError) as ei:
        derive_launch(shape, 1, 3, 4, tile=(512, 512, 512))
    msg = str(ei.value)
    assert "explicit tile" in msg and "MiB" in msg
    assert "REPRO_VMEM_LIMIT_BYTES" in msg
    # the same footprint is admitted when the device really has the room
    derive_launch(shape, 1, 3, 4, tile=(512, 512, 512),
                  vmem_limit=8 << 30)
    # LaunchFootprintError IS a ValueError: existing callers' handlers hold
    assert issubclass(LaunchFootprintError, ValueError)


def test_preflight_env_override(monkeypatch):
    tile = (64, 64, 64)
    # the whole launch as Mosaic allocates it: double-buffered windows and
    # output block plus the body's intermediates (the tile spans the
    # array, so no axis carries a halo)
    window = launch_footprint_bytes((64, 64, 64), tile, 1, [(0, 0, 0)] * 3,
                                    4, 1)
    assert window >= 2 * 4 * 4 * 64 ** 3
    monkeypatch.setenv("REPRO_VMEM_LIMIT_BYTES", str(window - 1))
    with pytest.raises(LaunchFootprintError):
        derive_launch((64, 64, 64), 1, 3, 4, tile=tile)
    monkeypatch.setenv("REPRO_VMEM_LIMIT_BYTES", str(window))
    derive_launch((64, 64, 64), 1, 3, 4, tile=tile)
    # explicit argument beats the env override
    with pytest.raises(LaunchFootprintError):
        preflight_vmem(tile, window, vmem_limit=window - 1,
                       explicit_tile=True)


def test_preflight_normal_derivation_passes():
    # auto-derived blocks honor the SOFT budget (8 MiB), far under the
    # hard limit — derivation never trips the admission check on its own
    for shape in [(512, 512, 512), (96, 64, 384), (17, 34, 51)]:
        derive_launch(shape, radius=1, n_fields=3, itemsize=4)


def test_derive_launch_alignment_preferences():
    """Minor axis prefers 128-lane multiples, next-to-minor 8-sublane
    multiples, whenever the extents allow it."""
    _, block = derive_launch((64, 64, 256), 1, 3, 4)
    assert block[-1] % 128 == 0
    assert block[-2] % 8 == 0
    # extents with no aligned divisor still yield a valid launch
    grid, block = derive_launch((17, 34, 51), 1, 3, 4)
    assert all(g * b == s for g, b, s in zip(grid, block, (17, 34, 51)))


def test_derive_launch_nsteps_halo_arithmetic():
    """Temporal blocking widens the VMEM halo to nsteps*radius: the same
    budget must yield a window set that still fits, and the halo term in
    the working set follows k*r."""
    shape = (256, 256, 256)
    budget = 2 << 20
    for radius, nsteps in [(1, 2), (1, 4), (2, 2)]:
        grid, block = derive_launch(shape, radius, 3, 4, vmem_budget=budget,
                                    nsteps=nsteps)
        halo = radius * nsteps
        window = 3 * np.prod([b + 2 * halo for b in block]) * 4
        assert window <= budget, (radius, nsteps, block)
        assert all(s % b == 0 for s, b in zip(shape, block))
    # deeper blocking can only shrink (or keep) the block volume
    _, b1 = derive_launch(shape, 1, 3, 4, vmem_budget=budget, nsteps=1)
    _, b4 = derive_launch(shape, 1, 3, 4, vmem_budget=budget, nsteps=4)
    assert np.prod(b4) <= np.prod(b1)


def test_launch_info_exposed(rng):
    ps = init_parallel_stencil(backend="pallas", ndims=2)

    @ps.parallel(outputs=("U2",))
    def k(U2, U):
        return {"U2": fd2d.inn(U) * 2.0}

    U = jnp.asarray(rng.rand(16, 128), jnp.float32)
    k(U2=U, U=U)
    info = list(k.launch_info.values())[0]
    assert info["grid"] and info["block"] and info["window_bytes"] > 0
