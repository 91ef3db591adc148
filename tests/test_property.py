"""Property-based tests (hypothesis) on the system's invariants."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

hypothesis = pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

from repro.core import boundary, fd2d, fd3d, init_parallel_stencil
from repro.distributed import compression
from repro.kernels import ref

SETTINGS = dict(max_examples=20, deadline=None)


@given(nx=st.integers(6, 24), ny=st.integers(6, 24), nz=st.integers(6, 20),
       seed=st.integers(0, 2**31 - 1))
@settings(**SETTINGS)
def test_pallas_stencil_matches_jnp_any_shape(nx, ny, nz, seed):
    """The Pallas backend equals the jnp backend for arbitrary shapes
    (launch derivation must handle awkward extents)."""
    rng = np.random.RandomState(seed)
    T = jnp.asarray(rng.rand(nx, ny, nz), jnp.float32)
    Ci = jnp.asarray(rng.rand(nx, ny, nz) + 0.5, jnp.float32)

    def kern(T2, T, Ci, dt):
        return {"T2": fd3d.inn(T) + dt * fd3d.inn(Ci) * (
            fd3d.d2_xi(T) + fd3d.d2_yi(T) + fd3d.d2_zi(T))}

    outs = []
    for backend in ("jnp", "pallas"):
        ps = init_parallel_stencil(backend=backend, ndims=3)
        k = ps.parallel(outputs=("T2",))(kern)
        outs.append(np.asarray(k(T2=T, T=T, Ci=Ci, dt=1e-3)))
    np.testing.assert_allclose(outs[0], outs[1], atol=5e-6)


@given(seed=st.integers(0, 2**31 - 1), n=st.integers(8, 32))
@settings(**SETTINGS)
def test_diffusion_max_principle(seed, n):
    """Explicit diffusion under the stability bound never creates new
    extrema (discrete maximum principle)."""
    rng = np.random.RandomState(seed)
    T = jnp.asarray(rng.rand(n, n, n), jnp.float32)
    inv = float(n - 1)
    dt = 1.0 / (inv ** 2) / 6.1  # paper's bound with lam/Ci = 1
    out = ref.diffusion3d_step(T, T, jnp.ones_like(T), 1.0, dt, inv, inv, inv)
    assert float(out.max()) <= float(T.max()) + 1e-6
    assert float(out.min()) >= float(T.min()) - 1e-6


@given(nx=st.integers(4, 24), ny=st.integers(4, 24), i=st.integers(0, 4),
       w=st.integers(3, 8), seed=st.integers(0, 2**31 - 1))
@settings(**SETTINGS)
def test_staggered_operator_shape_and_window_contract(nx, ny, i, w, seed):
    """The staggered operators d_xa/av_xa (and y-analogues) must (a) shave
    exactly one point off the differentiated axis and nothing else, and
    (b) commute with window extraction — evaluating on a sub-window equals
    slicing the full-array result. (b) is the contract that lets one
    kernel source run on full arrays AND halo-extended Pallas windows."""
    rng = np.random.RandomState(seed)
    A = jnp.asarray(rng.randn(nx, ny), jnp.float32)
    assert fd2d.d_xa(A).shape == (nx - 1, ny)
    assert fd2d.av_xa(A).shape == (nx - 1, ny)
    assert fd2d.d_ya(A).shape == (nx, ny - 1)
    assert fd2d.av_ya(A).shape == (nx, ny - 1)
    np.testing.assert_allclose(np.asarray(fd2d.d_xa(A)),
                               np.diff(np.asarray(A), axis=0), rtol=1e-6)
    # window contract along the staggered axis
    lo = min(i, nx - 3)
    hi = min(lo + w, nx)
    win = A[lo:hi, :]
    np.testing.assert_array_equal(np.asarray(fd2d.d_xa(win)),
                                  np.asarray(fd2d.d_xa(A))[lo:hi - 1, :])
    np.testing.assert_array_equal(np.asarray(fd2d.av_xa(win)),
                                  np.asarray(fd2d.av_xa(A))[lo:hi - 1, :])


@given(nx=st.integers(6, 20), ny=st.integers(6, 24),
       seed=st.integers(0, 2**31 - 1))
@settings(**SETTINGS)
def test_pallas_staggered_fields_match_jnp_any_shape(nx, ny, seed):
    """Mixed-shape coupled systems (cell scalars + face-centered fluxes)
    agree between the jnp backend and pallas windows for arbitrary extents:
    staggered `@all`-write outputs and staggered inputs both round-trip."""
    rng = np.random.RandomState(seed)
    phi = jnp.asarray(rng.rand(nx, ny), jnp.float32)
    Pe = jnp.asarray(rng.rand(nx, ny), jnp.float32)
    qx0 = jnp.zeros((nx - 1, ny), jnp.float32)
    qy0 = jnp.zeros((nx, ny - 1), jnp.float32)

    def flux(qx, qy, phi, Pe):
        k = (phi + 0.5) ** 2
        return {"qx": -fd2d.av_xa(k) * fd2d.d_xa(Pe),
                "qy": -fd2d.av_ya(k) * (fd2d.d_ya(Pe) - fd2d.av_ya(phi))}

    def upd(phi2, phi, Pe, qx, qy, dt):
        div_q = fd2d.d_xa(qx[:, 1:-1]) + fd2d.d_ya(qy[1:-1, :])
        return {"phi2": fd2d.inn(phi) - dt * (div_q + fd2d.inn(Pe))}

    outs = []
    for backend in ("jnp", "pallas"):
        ps = init_parallel_stencil(backend=backend, ndims=2)
        q = ps.parallel(outputs=("qx", "qy"))(flux)(
            qx=qx0, qy=qy0, phi=phi, Pe=Pe)
        phi2 = ps.parallel(outputs=("phi2",))(upd)(
            phi2=phi, phi=phi, Pe=Pe, qx=q["qx"], qy=q["qy"], dt=1e-2)
        outs.append((np.asarray(q["qx"]), np.asarray(q["qy"]),
                     np.asarray(phi2)))
    for a, b in zip(outs[0], outs[1]):
        np.testing.assert_allclose(a, b, atol=5e-6)


@given(seed=st.integers(0, 2**31 - 1))
@settings(**SETTINGS)
def test_periodic_diffusion_conserves_mass(seed):
    """With periodic ghost layers, one interior update conserves the total
    heat of the periodic cell (sum over interior)."""
    rng = np.random.RandomState(seed)
    n = 16
    T = jnp.asarray(rng.rand(n, n), jnp.float32)
    T = boundary.periodic(T)
    dt = 1e-2 / 4.0

    def kern(T2, T, dt):
        return {"T2": fd2d.inn(T) + dt * (fd2d.d2_xi(T) + fd2d.d2_yi(T))}

    ps = init_parallel_stencil(backend="jnp", ndims=2)
    out = ps.parallel(outputs=("T2",))(kern)(T2=T, T=T, dt=dt)
    before = float(jnp.sum(T[1:-1, 1:-1]))
    after = float(jnp.sum(out[1:-1, 1:-1]))
    assert abs(after - before) < 1e-3 * max(abs(before), 1.0)


@given(seed=st.integers(0, 2**31 - 1), scale=st.floats(1e-3, 1e3))
@settings(**SETTINGS)
def test_int8_quantization_error_bound(seed, scale):
    """Error of symmetric per-block int8 quantization is <= scale/254 per
    element (half a quantization step of the block's max)."""
    rng = np.random.RandomState(seed)
    g = jnp.asarray(rng.randn(1000) * scale, jnp.float32)
    q, s, meta = compression.quantize_int8(g)
    back = compression.dequantize_int8(q, s, meta)
    bound = float(jnp.max(jnp.abs(g))) / 254 + 1e-8
    assert float(jnp.max(jnp.abs(back - g))) <= bound * 1.01
