"""Core API coverage: Grid, FieldSet/VectorField (SoA/AoS, C5), boundary
conditions, T_eff accounting."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import Grid, FieldSet, VectorField, boundary, teff
from repro.core.grid import human_bytes, volume_bytes


def test_grid_properties():
    g = Grid((65, 33, 17), (1.0, 2.0, 4.0))
    assert g.spacing == (1.0 / 64, 2.0 / 32, 4.0 / 16)
    assert g.interior_shape == (63, 31, 15)
    assert g.n_points == 65 * 33 * 17
    dt = g.stable_diffusion_dt(2.0)
    assert dt == pytest.approx(min(g.spacing) ** 2 / 2.0 / 6.1)
    with pytest.raises(ValueError):
        Grid((2, 2), radius=1)


def test_grid_subgrid_decomposition():
    g = Grid((34, 34), (1.0, 1.0))
    sub = g.subgrid((2, 4))
    assert sub.shape == (18, 10)
    with pytest.raises(ValueError):
        g.subgrid((3, 4))  # 32 % 3 != 0


def test_fieldset_alloc_and_registry():
    g = Grid((8, 8, 8))
    fs = FieldSet(g, dtype=jnp.float32)
    T = fs.ones("T")
    C = fs.full(2.5, "C")
    assert T.shape == g.shape and float(C[0, 0, 0]) == 2.5
    x = fs.from_fn(lambda x, y, z: x + y + z, "X")
    assert float(x[-1, -1, -1]) == pytest.approx(3.0)
    assert set(fs.names()) == {"T", "C", "X"}
    assert fs.nbytes() == 3 * 8 ** 3 * 4


def test_vector_field_layouts():
    g = Grid((6, 6))
    fs = FieldSet(g, layout="soa")
    v = fs.vector(3, init=1.0, name="V")
    assert v.layout == "soa" and v.ncomp == 3
    assert v[0].shape == (6, 6)
    aos = v.as_aos()
    assert aos.components.shape == (6, 6, 3)
    np.testing.assert_array_equal(np.asarray(aos[1]), np.asarray(v[1]))
    back = aos.as_soa()
    assert back.layout == "soa" and len(back.components) == 3
    doubled = v.map(lambda c: c * 2)
    assert float(doubled[2][0, 0]) == 2.0


def test_boundary_conditions(rng):
    A = jnp.asarray(rng.rand(6, 6), jnp.float32)
    d = boundary.dirichlet(A, 9.0)
    assert float(d[0, 3]) == 9.0 and float(d[3, -1]) == 9.0
    n = boundary.neumann0(A, axes=(0,))
    np.testing.assert_array_equal(np.asarray(n[0]), np.asarray(n[1]))
    p = boundary.periodic(A, axes=(1,))
    np.testing.assert_array_equal(np.asarray(p[:, 0]), np.asarray(p[:, -2]))
    np.testing.assert_array_equal(np.asarray(p[:, -1]), np.asarray(p[:, 1]))


def test_boundary_depth2_dirichlet(rng):
    A = jnp.asarray(rng.rand(8, 9), jnp.float32)
    d = np.asarray(boundary.dirichlet(A, 7.0, depth=2))
    assert (d[:2] == 7.0).all() and (d[-2:] == 7.0).all()
    assert (d[:, :2] == 7.0).all() and (d[:, -2:] == 7.0).all()
    # interior untouched
    np.testing.assert_array_equal(d[2:-2, 2:-2], np.asarray(A)[2:-2, 2:-2])


def test_boundary_depth2_neumann0(rng):
    A = jnp.asarray(rng.rand(8, 9), jnp.float32)
    n = np.asarray(boundary.neumann0(A, axes=(0,), depth=2))
    # both face layers copy the matching interior source layers
    np.testing.assert_array_equal(n[0], n[2])
    np.testing.assert_array_equal(n[1], n[3])
    np.testing.assert_array_equal(n[-1], n[-3])
    np.testing.assert_array_equal(n[-2], n[-4])
    np.testing.assert_array_equal(n[2:-2], np.asarray(A)[2:-2])


def test_boundary_depth2_periodic(rng):
    A = jnp.asarray(rng.rand(9, 8), jnp.float32)
    p = np.asarray(boundary.periodic(A, axes=(0,), depth=2))
    a = np.asarray(A)
    # low ghosts mirror the far interior, high ghosts the near interior
    np.testing.assert_array_equal(p[0:2], a[-4:-2])
    np.testing.assert_array_equal(p[-2:], a[2:4])
    np.testing.assert_array_equal(p[2:-2], a[2:-2])


def test_boundary_face_smaller_than_depth_raises(rng):
    A = jnp.asarray(rng.rand(5, 12), jnp.float32)
    with pytest.raises(ValueError, match="smaller than"):
        boundary.dirichlet(A, 0.0, axes=(0,), depth=3)   # 5 < 2*3
    with pytest.raises(ValueError, match="smaller than"):
        boundary.neumann0(A, axes=(0,), depth=2)         # 5 < 3*2
    with pytest.raises(ValueError, match="smaller than"):
        boundary.periodic(A, axes=(0,), depth=2)
    with pytest.raises(ValueError, match="depth must be"):
        boundary.neumann0(A, axes=(1,), depth=0)
    # the depth that *does* fit still works on the same array
    boundary.dirichlet(A, 0.0, axes=(0,), depth=2)
    boundary.neumann0(A, axes=(1,), depth=4)


def test_teff_accounting():
    a = teff.a_eff(n_points=512 ** 3, n_read=2, n_write=1, itemsize=4)
    assert a == 3 * 512 ** 3 * 4
    # paper numbers: A100 93%, P100 88% at their measured T_eff
    assert teff.fraction(1262e9, teff.A100_SXM4) == pytest.approx(0.93, abs=0.01)
    assert teff.fraction(496e9, teff.P100_PCIE) == pytest.approx(0.88, abs=0.01)
    m = teff.measure(lambda: jnp.ones(16).block_until_ready(), iters=5, warmup=1)
    assert m.median_s > 0 and m.ci95_s[0] <= m.median_s <= m.ci95_s[1] * 1.5


def test_human_bytes():
    assert human_bytes(512) == "512.00 B"
    assert human_bytes(2 * 1024 ** 3) == "2.00 GiB"
    assert volume_bytes((4, 4), jnp.float32) == 64


@pytest.mark.parametrize("env_dir", [None, "/elsewhere/jax-cache"])
def test_compile_cache_placed_from_outside(monkeypatch, env_dir):
    """JAX_COMPILATION_CACHE_DIR, when set, is the cache and nothing else
    is configured; otherwise the cache sits at <checkout>/.jax_cache."""
    import os

    from repro import compile_cache

    before = jax.config.jax_compilation_cache_dir
    if env_dir is None:
        monkeypatch.delenv(compile_cache.ENV, raising=False)
    else:
        monkeypatch.setenv(compile_cache.ENV, env_dir)
    try:
        got = compile_cache.enable_compile_cache()
        if env_dir is None:
            root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
            assert got == os.path.join(root, ".jax_cache")
            assert jax.config.jax_compilation_cache_dir == got
        else:
            assert got == env_dir
            assert jax.config.jax_compilation_cache_dir == before
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
