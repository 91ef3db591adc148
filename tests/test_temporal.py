"""Temporal blocking (nsteps=k): the k-step fused path must be
bitwise-consistent with k sequential single-step calls (double-buffer
rotation) on the jnp and pallas-interpret backends, for the generic
StencilKernel and the hand-specialized diffusion3d kernel, plus the
autotuner and the blocked T_eff accounting."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import fd2d, fd3d, init_parallel_stencil, teff
from repro.kernels import autotune, diffusion3d, ref

SHAPE = (20, 16, 24)
SC = dict(lam=1.0, dt=1e-4, _dx=float(SHAPE[0] - 1), _dy=float(SHAPE[1] - 1),
          _dz=float(SHAPE[2] - 1))


def _diffusion_kernel(ps):
    @ps.parallel(outputs=("T2",), rotations={"T2": "T"})
    def kern(T2, T, Ci, lam, dt, _dx, _dy, _dz):
        return {"T2": fd3d.inn(T) + dt * (lam * fd3d.inn(Ci) * (
            fd3d.d2_xi(T) * _dx ** 2 + fd3d.d2_yi(T) * _dy ** 2 +
            fd3d.d2_zi(T) * _dz ** 2))}
    return kern


def _fields(rng):
    T = jnp.asarray(rng.rand(*SHAPE), jnp.float32)
    return T.copy(), T, jnp.asarray(rng.rand(*SHAPE) + 0.5, jnp.float32)


def _sequential(kern, T2, T, Ci, k):
    a, b = T2, T
    for _ in range(k):
        a = kern(T2=a, T=b, Ci=Ci, **SC)
        a, b = b, a
    return np.asarray(b)


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
@pytest.mark.parametrize("k", [1, 2, 4])
def test_run_steps_bitwise_matches_sequential(backend, k, rng):
    T2, T, Ci = _fields(rng)
    kern = _diffusion_kernel(init_parallel_stencil(backend=backend, ndims=3))
    want = _sequential(kern, T2, T, Ci, k)
    got = np.asarray(kern.run_steps(k, T2=T2, T=T, Ci=Ci, **SC))
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("k", [2, 4])
def test_run_steps_backends_agree(k, rng):
    T2, T, Ci = _fields(rng)
    outs = {}
    for backend in ("jnp", "pallas"):
        kern = _diffusion_kernel(init_parallel_stencil(backend=backend, ndims=3))
        outs[backend] = np.asarray(kern.run_steps(k, T2=T2, T=T, Ci=Ci, **SC))
    np.testing.assert_allclose(outs["jnp"], outs["pallas"], atol=5e-6)


@pytest.mark.parametrize("k", [1, 2, 4])
def test_hand_diffusion3d_nsteps_bitwise(k, rng):
    T2, T, Ci = _fields(rng)
    args = (1.0, 1e-4, SC["_dx"], SC["_dy"], SC["_dz"])
    a, b = T2, T
    for _ in range(k):
        a = diffusion3d.diffusion3d_step(a, b, Ci, *args)
        a, b = b, a
    want = np.asarray(b)
    got = np.asarray(diffusion3d.diffusion3d_step(T2, T, Ci, *args, nsteps=k))
    np.testing.assert_array_equal(got, want)
    # and the fused result still tracks the jnp oracle chain
    a, b = T2, T
    for _ in range(k):
        a = ref.diffusion3d_step(a, b, Ci, *args)
        a, b = b, a
    np.testing.assert_allclose(got, np.asarray(b), atol=5e-6)


def test_nsteps_boundary_preserved(rng):
    """k-step fused launches keep the write buffer's boundary ring, exactly
    like a single step (the paper's @inn semantics)."""
    T = jnp.asarray(rng.rand(*SHAPE), jnp.float32)
    T = T.at[0].set(3.0).at[-1].set(3.0)
    T = T.at[:, 0].set(3.0).at[:, -1].set(3.0)
    T = T.at[:, :, 0].set(3.0).at[:, :, -1].set(3.0)
    T2 = T.copy()
    Ci = jnp.ones(SHAPE, jnp.float32)
    got = np.asarray(diffusion3d.diffusion3d_step(
        T2, T, Ci, 1.0, 1e-4, SC["_dx"], SC["_dy"], SC["_dz"], nsteps=4))
    np.testing.assert_array_equal(got[0], 3.0)
    np.testing.assert_array_equal(got[-1], 3.0)
    np.testing.assert_array_equal(got[:, 0], 3.0)
    np.testing.assert_array_equal(got[:, :, -1], 3.0)


def test_run_steps_2d_multi_sweep(rng):
    shape = (24, 32)
    U = jnp.asarray(rng.rand(*shape), jnp.float32)
    ps = init_parallel_stencil(backend="pallas", ndims=2)

    @ps.parallel(outputs=("U2",), rotations={"U2": "U"})
    def kern(U2, U, dt):
        return {"U2": fd2d.inn(U) + dt * (fd2d.d2_xi(U) + fd2d.d2_yi(U))}

    a, b = U.copy(), U
    for _ in range(3):
        a = kern(U2=a, U=b, dt=1e-3)
        a, b = b, a
    got = np.asarray(kern.run_steps(3, U2=U.copy(), U=U, dt=1e-3))
    np.testing.assert_array_equal(got, np.asarray(b))


def test_run_steps_requires_rotations(rng):
    ps = init_parallel_stencil(backend="jnp", ndims=2)

    @ps.parallel(outputs=("U2",))
    def kern(U2, U, dt):
        return {"U2": fd2d.inn(U) * 2.0}

    U = jnp.asarray(rng.rand(8, 8), jnp.float32)
    with pytest.raises(ValueError, match="rotations"):
        kern.run_steps(2, U2=U, U=U, dt=0.1)
    # nsteps=1 never needs rotations
    kern.run_steps(1, U2=U, U=U, dt=0.1)


# --------------------------------------------------------------------------
# blocked T_eff accounting
# --------------------------------------------------------------------------
def test_a_eff_blocked_divides_by_k():
    base = teff.a_eff(1000, n_read=2, n_write=1, itemsize=4)
    assert teff.a_eff_blocked(1000, 2, 1, 4, nsteps=1) == base
    assert teff.a_eff_blocked(1000, 2, 1, 4, nsteps=4) == base / 4


def test_halo_compute_overhead_monotone():
    """Redundant halo compute grows with k and shrinks with block size."""
    assert teff.halo_compute_overhead((32, 32, 32), 1, 1) == 0.0
    o2 = teff.halo_compute_overhead((32, 32, 32), 1, 2)
    o4 = teff.halo_compute_overhead((32, 32, 32), 1, 4)
    assert 0.0 < o2 < o4
    assert teff.halo_compute_overhead((64, 64, 64), 1, 4) < o4


# --------------------------------------------------------------------------
# autotuner
# --------------------------------------------------------------------------
def test_autotune_picks_and_caches(tmp_path):
    cache = str(tmp_path / "tune.json")
    calls = []

    def make_step(tile, k):
        def run():
            calls.append((tile, k))
            return jnp.zeros(())
        return run

    r1 = autotune.autotune(
        make_step, shape=(16, 16, 16), dtype="float32", radius=1, n_fields=3,
        nsteps_candidates=(1, 2), iters=1, tag="unit", cache_path=cache)
    assert r1.nsteps in (1, 2) and len(r1.tile) == 3
    assert r1.candidates_tried >= 2
    n_calls = len(calls)
    # second invocation: memoized, no new measurements
    r2 = autotune.autotune(
        make_step, shape=(16, 16, 16), dtype="float32", radius=1, n_fields=3,
        nsteps_candidates=(1, 2), iters=1, tag="unit", cache_path=cache)
    assert r2 == r1 and len(calls) == n_calls
    # disk cache survives a cold in-process cache
    autotune._CACHE.clear()
    r3 = autotune.autotune(
        make_step, shape=(16, 16, 16), dtype="float32", radius=1, n_fields=3,
        nsteps_candidates=(1, 2), iters=1, tag="unit", cache_path=cache)
    assert r3.tile == r1.tile and r3.nsteps == r1.nsteps
    assert len(calls) == n_calls


def test_autotune_skips_only_unrealizable_tiles_on_device():
    """A candidate that raises anything but UnrealizableTile (a compiler
    refusal on the device) is a fault the tuner re-raises, on every
    backend; tiles unrealizable by construction are skipped."""
    from repro.kernels.stencil import TileNotCompilable, UnrealizableTile

    def make_step(fail):
        def build(tile, k):
            if k == 2:
                raise fail
            return lambda: jnp.zeros(())
        return build

    kw = dict(shape=(16, 16, 16), dtype="float32", radius=1, n_fields=3,
              nsteps_candidates=(1, 2), iters=1)
    autotune._CACHE.clear()
    for i, fail in enumerate([UnrealizableTile("no"),
                              TileNotCompilable("splits a minor axis")]):
        r = autotune.autotune(make_step(fail), tag=f"u1-{i}", **kw)
        assert r.nsteps == 1
    with pytest.raises(RuntimeError, match="refused"):
        autotune.autotune(make_step(RuntimeError("Mosaic refused it")),
                          tag="u2", **kw)


def test_autotune_tile_candidates_keep_the_tpu_layout():
    """Every derived candidate keeps the two minor axes whole (the only
    tiles Mosaic compiles) and fits the budget as the preflight counts it."""
    from repro.kernels import stencil

    for shape in [(512,) * 3, (64, 64, 256), (16, 16, 16)]:
        cands = autotune.tile_candidates(shape, 1, 3, 4)
        assert cands and len(set(cands)) == len(cands)
        for t in cands:
            assert t[1:] == shape[1:] and shape[0] % t[0] == 0
            assert stencil.launch_footprint_bytes(
                shape, t, 1, [(0,) * 3] * 3, 4, 1) <= \
                stencil.DEFAULT_VMEM_BUDGET
    assert len(autotune.tile_candidates((512,) * 3, 1, 3, 4)) >= 2


def test_autotune_diffusion3d_smoke():
    r = autotune.autotune_diffusion3d((16, 16, 16), nsteps_candidates=(1, 2),
                                      iters=1)
    assert r.nsteps in (1, 2) and r.per_step_s > 0
