"""Finite-difference operator semantics (the math-close layer, paper C2)."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import fd as fd_mod
from repro.core.fd import fd1d, fd2d, fd3d


@pytest.mark.parametrize("fd,nd", [(fd1d, 1), (fd2d, 2), (fd3d, 3)])
def test_shapes(fd, nd, rng):
    A = jnp.asarray(rng.rand(*(7,) * nd), jnp.float32)
    assert fd.inn(A).shape == (5,) * nd
    assert fd.av(A).shape == (6,) * nd
    assert fd.maxloc(A).shape == (5,) * nd
    names = "xyz"[:nd]
    for ax, nm in enumerate(names):
        da = getattr(fd, f"d_{nm}a")(A)
        assert da.shape[ax] == 6 and all(
            s == 7 for i, s in enumerate(da.shape) if i != ax)
        di = getattr(fd, f"d_{nm}i")(A)
        assert di.shape[ax] == 6 and all(
            s == 5 for i, s in enumerate(di.shape) if i != ax)
        d2 = getattr(fd, f"d2_{nm}i")(A)
        assert d2.shape == (5,) * nd


def test_d2_is_d_of_d(rng):
    A = jnp.asarray(rng.rand(9, 9, 9), jnp.float32)
    # d2_xi == d_xa applied twice then inner in y,z
    dd = fd3d.d_xa(fd3d.d_xa(A))[:, 1:-1, 1:-1]
    np.testing.assert_allclose(fd3d.d2_xi(A), dd, rtol=1e-5, atol=1e-6)


def test_linear_field_has_zero_laplacian():
    x, y, z = jnp.meshgrid(*(jnp.linspace(0, 1, 8),) * 3, indexing="ij")
    A = 2.0 * x + 3.0 * y - z
    lap = fd3d.laplacian(A, (7.0, 7.0, 7.0))
    np.testing.assert_allclose(np.asarray(lap), 0.0, atol=1e-4)


def test_quadratic_field_has_constant_laplacian():
    n = 16
    xs = jnp.linspace(0.0, 1.0, n)
    x, y, z = jnp.meshgrid(xs, xs, xs, indexing="ij")
    A = x ** 2
    inv = float(n - 1)
    lap = fd3d.laplacian(A, (inv, inv, inv))
    np.testing.assert_allclose(np.asarray(lap), 2.0, rtol=1e-3)


def test_av_is_midpoint(rng):
    A = jnp.asarray(rng.rand(6, 6), jnp.float32)
    got = fd2d.av(A)
    want = (A[1:, 1:] + A[1:, :-1] + A[:-1, 1:] + A[:-1, :-1]) / 4
    np.testing.assert_allclose(got, want, rtol=1e-6)


def test_maxloc_dominates_inn(rng):
    A = jnp.asarray(rng.rand(8, 8, 8), jnp.float32)
    assert bool(jnp.all(fd3d.maxloc(A) >= fd3d.inn(A)))


def test_operators_are_linear(rng):
    A = jnp.asarray(rng.rand(8, 8, 8), jnp.float32)
    B = jnp.asarray(rng.rand(8, 8, 8), jnp.float32)
    for op in (fd3d.d2_xi, fd3d.d_ya, fd3d.av, fd3d.inn):
        np.testing.assert_allclose(op(2 * A + 3 * B), 2 * op(A) + 3 * op(B),
                                   rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("axes,name", [((0, 1), "xy"), ((0, 2), "xz"),
                                       ((1, 2), "yz")])
@pytest.mark.parametrize("kind", ["a", "i"])
def test_two_axis_averages_match_parallelstencil(axes, name, kind, rng):
    """``av_xyi``-style averages are ParallelStencil's FiniteDifferences3D
    macros: ``(A[i,j,k] + A[i+1,j,k] + A[i,j+1,k] + A[i+1,j+1,k]) * 0.25``
    over the two named axes, the third axis whole (``a``) or its interior
    (``i``, index + 1), element by element."""
    A = np.asarray(rng.rand(6, 7, 8), np.float32)
    got = np.asarray(getattr(fd3d, f"av_{name}{kind}")(jnp.asarray(A)))
    (c,) = set(range(3)) - set(axes)
    shape = [s - 1 if a in axes else (s if kind == "a" else s - 2)
             for a, s in enumerate(A.shape)]
    assert got.shape == tuple(shape)
    want = np.empty(shape, np.float32)
    for idx in np.ndindex(*shape):
        base = list(idx)
        base[c] += kind == "i"

        def at(da, db):
            p = list(base)
            p[axes[0]] += da
            p[axes[1]] += db
            return A[tuple(p)]

        want[idx] = (at(0, 0) + at(1, 0) + at(0, 1) + at(1, 1)) * 0.25
    np.testing.assert_array_equal(got, want)
