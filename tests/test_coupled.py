"""Coupled multi-field engine: multiple outputs per launch, mixed-shape
staggered fields, per-axis write-mode derivation, k-step coupled rotation
(bitwise vs sequential), and the error surface for inconsistent systems."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import fd2d as fd, init_parallel_stencil
from repro.kernels import autotune
from repro.kernels.stencil import derive_launch, field_geometry

SHAPE = (20, 24)


def _arr(rng, shape=SHAPE):
    return jnp.asarray(rng.rand(*shape), jnp.float32)


def _coupled_kernel(ps):
    """Two diffusing fields with a reaction coupling."""
    @ps.parallel(outputs=("A2", "B2"), rotations={"A2": "A", "B2": "B"})
    def kern(A2, B2, A, B, dt):
        return {
            "A2": fd.inn(A) + dt * (fd.d2_xi(A) + fd.d2_yi(A)) + dt * fd.inn(B),
            "B2": fd.inn(B) + dt * (fd.d2_xi(B) + fd.d2_yi(B)) - dt * fd.inn(A),
        }
    return kern


def _stag_kernel(ps):
    """Cell field T coupled to a rotated face-centered field q (x-faces)."""
    @ps.parallel(outputs=("T2", "q2"), rotations={"T2": "T", "q2": "q"})
    def kern(T2, q2, T, q, dt):
        return {"T2": fd.inn(T) + dt * fd.d_xi(q),
                "q2": 0.7 * q + 0.3 * fd.av_xa(T)}
    return kern


# --------------------------------------------------------------------------
# coupled k-step rotation
# --------------------------------------------------------------------------
@pytest.mark.parametrize("backend", ["jnp", "pallas"])
@pytest.mark.parametrize("k", [2, 3])
def test_coupled_run_steps_bitwise_matches_sequential(backend, k, rng):
    A, B = _arr(rng), _arr(rng)
    kern = _coupled_kernel(init_parallel_stencil(backend=backend, ndims=2))
    a, b, a2, b2 = A, B, A.copy(), B.copy()
    for _ in range(k):
        o = kern(A2=a2, B2=b2, A=a, B=b, dt=1e-3)
        a, a2 = o["A2"], a
        b, b2 = o["B2"], b
    got = kern.run_steps(k, A2=A.copy(), B2=B.copy(), A=A, B=B, dt=1e-3)
    np.testing.assert_array_equal(np.asarray(got["A2"]), np.asarray(a))
    np.testing.assert_array_equal(np.asarray(got["B2"]), np.asarray(b))


@pytest.mark.parametrize("k", [2, 3])
def test_coupled_run_steps_backends_agree(k, rng):
    A, B = _arr(rng), _arr(rng)
    outs = {}
    for backend in ("jnp", "pallas"):
        kern = _coupled_kernel(init_parallel_stencil(backend=backend, ndims=2))
        outs[backend] = kern.run_steps(k, A2=A.copy(), B2=B.copy(), A=A, B=B,
                                       dt=1e-3)
    for o in ("A2", "B2"):
        np.testing.assert_allclose(np.asarray(outs["jnp"][o]),
                                   np.asarray(outs["pallas"][o]), atol=5e-6)


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_staggered_rotation_run_steps_bitwise(backend, rng):
    """A face-centered field in the double-buffer rotation: the fused
    3-step launch must equal 3 sequential coupled calls bit-for-bit."""
    T, q = _arr(rng), _arr(rng, (SHAPE[0] - 1, SHAPE[1]))
    kern = _stag_kernel(init_parallel_stencil(backend=backend, ndims=2))
    a, b, qa, qb = T, T.copy(), q, q.copy()
    for _ in range(3):
        o = kern(T2=b, q2=qb, T=a, q=qa, dt=1e-3)
        a, b = o["T2"], a
        qa, qb = o["q2"], qa
    got = kern.run_steps(3, T2=T.copy(), q2=q.copy(), T=T, q=q, dt=1e-3)
    np.testing.assert_array_equal(np.asarray(got["T2"]), np.asarray(a))
    np.testing.assert_array_equal(np.asarray(got["q2"]), np.asarray(qa))


# --------------------------------------------------------------------------
# mixed-shape staggered fields, single step
# --------------------------------------------------------------------------
def test_staggered_all_write_outputs_backend_parity(rng):
    """Face-centered flux outputs (`@all` write derived from the update's
    full-window extent) agree between backends, including at the domain
    boundary faces."""
    n, m = SHAPE
    phi, Pe = _arr(rng), _arr(rng)
    qx0 = jnp.zeros((n - 1, m), jnp.float32)
    qy0 = jnp.zeros((n, m - 1), jnp.float32)

    def flux(qx, qy, phi, Pe, dx, dy):
        k = (phi + 0.5) ** 2
        return {"qx": -fd.av_xa(k) * fd.d_xa(Pe) / dx,
                "qy": -fd.av_ya(k) * (fd.d_ya(Pe) / dy - 3.0 * fd.av_ya(phi))}

    outs = {}
    for backend in ("jnp", "pallas"):
        ps = init_parallel_stencil(backend=backend, ndims=2)
        kern = ps.parallel(outputs=("qx", "qy"))(flux)
        outs[backend] = kern(qx=qx0, qy=qy0, phi=phi, Pe=Pe, dx=0.1, dy=0.1)
    assert outs["jnp"]["qx"].shape == (n - 1, m)
    assert outs["jnp"]["qy"].shape == (n, m - 1)
    for o in ("qx", "qy"):
        assert outs["pallas"][o].shape == outs["jnp"][o].shape
        np.testing.assert_allclose(np.asarray(outs["jnp"][o]),
                                   np.asarray(outs["pallas"][o]), atol=1e-6)


def test_mixed_shape_inputs_backend_parity(rng):
    """Cell-centered outputs consuming face-centered inputs (the porosity
    flux-split update) agree between backends."""
    n, m = SHAPE
    phi, Pe = _arr(rng), _arr(rng)
    qx = _arr(rng, (n - 1, m))
    qy = _arr(rng, (n, m - 1))

    def upd(phi2, Pe2, phi, Pe, qx, qy, dtau):
        div_q = fd.d_xa(qx[:, 1:-1]) / 0.1 + fd.d_ya(qy[1:-1, :]) / 0.1
        Pe_new = fd.inn(Pe) + dtau * (-(div_q + fd.inn(Pe)))
        phi_new = fd.inn(phi) + dtau * (-(1.0 - fd.inn(phi)) * Pe_new)
        return {"Pe2": Pe_new, "phi2": phi_new}

    outs = {}
    for backend in ("jnp", "pallas"):
        ps = init_parallel_stencil(backend=backend, ndims=2)
        kern = ps.parallel(outputs=("phi2", "Pe2"))(upd)
        outs[backend] = kern(phi2=phi, Pe2=Pe, phi=phi, Pe=Pe, qx=qx, qy=qy,
                             dtau=0.01)
    for o in ("phi2", "Pe2"):
        np.testing.assert_allclose(np.asarray(outs["jnp"][o]),
                                   np.asarray(outs["pallas"][o]), atol=1e-6)


def test_all_write_collocated_covers_boundary(rng):
    """A full-extent update on a cell-centered output writes the boundary
    ring too (`@all` semantics on off=0 axes)."""
    U = _arr(rng)
    for backend in ("jnp", "pallas"):
        ps = init_parallel_stencil(backend=backend, ndims=2)

        @ps.parallel(outputs=("U2",))
        def kern(U2, U):
            return {"U2": 2.0 * U}

        got = np.asarray(kern(U2=jnp.zeros_like(U), U=U))
        np.testing.assert_allclose(got, 2.0 * np.asarray(U), atol=1e-6)


# --------------------------------------------------------------------------
# error surface
# --------------------------------------------------------------------------
def test_inconsistent_field_shape_raises(rng):
    ps = init_parallel_stencil(backend="pallas", ndims=2)

    @ps.parallel(outputs=("U2",))
    def kern(U2, U, W):
        return {"U2": fd.inn(U)}

    U = _arr(rng)
    with pytest.raises(ValueError, match="staggering band"):
        kern(U2=U, U=U, W=jnp.zeros((8, 8), jnp.float32))


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_staggered_interior_write_raises(backend, rng):
    """On the legacy declared-radius geometry (an update the footprint
    tracer cannot read) an `inn`-style write on a staggered axis would
    leave block-boundary faces unwritten — rejected with a pointed
    message on BOTH backends (a kernel that traces on jnp must trace on
    pallas and vice versa). The inferred footprint reaches past the
    stagger and takes such a write (next test)."""
    ps = init_parallel_stencil(backend=backend, ndims=2)

    @ps.parallel(outputs=("q2",), radius=1)
    def kern(q2, q, T):
        return {"q2": jnp.tanh(fd.inn(q))}

    q = _arr(rng, (SHAPE[0] - 1, SHAPE[1]))
    with pytest.raises(ValueError, match="staggered along axis 0"):
        kern(q2=q, q=q, T=_arr(rng))


@pytest.mark.parametrize("tile", [None, 1, 2, 3])
def test_staggered_interior_write_with_free_slip(tile):
    """ParallelStencil's ``@inn(Vy) = @inn(Vy) + @all(dVy)`` on face fields
    staggered along the tiled axis 0, with zero-gradient walls fused on two
    axes each (the Stokes velocity update): the inferred window reaches
    ``offset + ring`` past each block, so blocks of any depth along axis 0,
    one plane too, write exactly what the jnp backend writes."""
    from repro.core import fd3d
    from repro.ir import BoundaryCondition

    n, rng = 11, np.random.RandomState(5)

    def arr(*shape):
        return jnp.asarray(rng.rand(*shape), jnp.float32)

    fields = dict(Vx=arr(n + 1, n, n), Vy=arr(n, n + 1, n),
                  dVx=arr(n - 1, n - 2, n - 2), dVy=arr(n - 2, n - 1, n - 2))
    walls = {"Vx": BoundaryCondition("neumann0", axes=(1, 2)),
             "Vy": BoundaryCondition("neumann0", axes=(0, 2))}
    outs = {}
    for backend, t in (("jnp", None), ("pallas", tile)):
        ps = init_parallel_stencil(backend=backend, ndims=3)

        @ps.parallel(outputs=("Vx", "Vy"), bc=walls,
                     tile=None if t is None else (t, n + 1, n))
        def velocities(Vx, Vy, dVx, dVy):
            return {"Vx": fd3d.inn(Vx) + dVx, "Vy": fd3d.inn(Vy) + dVy}

        outs[backend] = velocities(**fields)
        if t is not None:
            (info,) = velocities.launch_info.values()
            assert info["block"][0] == t
    for o in ("Vx", "Vy"):
        np.testing.assert_array_equal(np.asarray(outs["pallas"][o]),
                                      np.asarray(outs["jnp"][o]))


def _own_extent_kernel(ps, case, n, tile):
    """A launch whose outputs are staggered against its base extent, in
    the geometry ``case`` names, and its fields."""
    from repro.core import fd3d
    from repro.ir import BoundaryCondition

    rng = np.random.RandomState(7)

    def arr(*shape):
        return jnp.asarray(rng.rand(*shape), jnp.float32)

    if case == "streamed-minor":
        # streamed along axis 0; F is staggered on the minor axis 1 only
        @ps.parallel(outputs=("T2", "F"), march_axis=0, tile=tile)
        def kern(T2, F, T):
            return {"T2": fd3d.inn(T) + 0.1 * fd3d.d2_xi(T),
                    "F": fd3d.d_ya(T) - 0.5 * fd3d.av_ya(T)}

        return kern, dict(T2=arr(n, n, n), F=arr(n, n - 1, n), T=arr(n, n, n))
    if case == "neumann0":
        # compute_V: Vy is one plane short of the base on axis 0
        walls = {"Vx": BoundaryCondition("neumann0", axes=(1, 2)),
                 "Vy": BoundaryCondition("neumann0", axes=(0, 2))}

        @ps.parallel(outputs=("Vx", "Vy"), bc=walls, tile=tile)
        def kern(Vx, Vy, dVx, dVy):
            return {"Vx": fd3d.inn(Vx) + dVx, "Vy": fd3d.inn(Vy) + dVy}

        return kern, dict(Vx=arr(n + 1, n, n), Vy=arr(n, n + 1, n),
                          dVx=arr(n - 1, n - 2, n - 2),
                          dVy=arr(n - 2, n - 1, n - 2))
    # compute_tau's shapes: the base is (n+1, n+1, n); P is one plane
    # short of it on axes 0 and 1, txy two on axis 0
    reds = {"ss": "sum_sq(txy)"} if case == "sum-sq" else None

    @ps.parallel(outputs=("P", "txy"), tile=tile, reductions=reds)
    def kern(P, txy, Vx, Vy):
        return {"P": P - 0.5 * (fd3d.d_xa(Vx) + fd3d.d_ya(Vy)),
                "txy": 0.5 * txy + (fd3d.d_yi(Vx) + fd3d.d_xi(Vy))}

    return kern, dict(P=arr(n, n, n), txy=arr(n - 1, n - 1, n - 2),
                      Vx=arr(n + 1, n, n), Vy=arr(n, n + 1, n))


@pytest.mark.parametrize("case,tile", [
    # 1-plane blocks: P's last block and txy's last two lie wholly past
    # their extents (compute_tau's geometry)
    ("one-plane", (1, 8, 7)),
    # 4-plane blocks: the last block of each output is ragged
    ("ragged", (4, 8, 7)),
    # both leading axes tiled with clamped windows: the staged cut
    ("realigned", (2, 4, 7)),
    ("streamed-minor", (1, 7, 7)),
    ("sum-sq", (1, 8, 7)),
    ("neumann0", (2, 8, 7)),
])
def test_staggered_output_stored_at_own_extent(case, tile):
    """A staggered output leaves the launch at its own extent, bit for bit
    what the jnp backend writes, whichever of its blocks are ragged, wholly
    past its extent, realigned or streamed; a fused ``sum_sq`` over it and
    a fused ``neumann0`` on it see the same cells."""
    n = 7
    outs, kerns = {}, {}
    for backend, t in (("jnp", None), ("pallas", tile)):
        kern, fields = _own_extent_kernel(
            init_parallel_stencil(backend=backend, ndims=3), case, n, t)
        outs[backend], kerns[backend] = kern(**fields), kern
    (info,) = kerns["pallas"].launch_info.values()
    assert info["block"] == tile
    assert info["march_axis"] == (0 if case == "streamed-minor" else None)
    if case == "sum-sq":
        (got, reds), (want, want_reds) = outs["pallas"], outs["jnp"]
        np.testing.assert_allclose(
            float(reds["ss"]), float(jnp.sum(got["txy"] ** 2)), rtol=1e-6)
        np.testing.assert_allclose(float(reds["ss"]),
                                   float(want_reds["ss"]), rtol=1e-6)
    else:
        got, want = outs["pallas"], outs["jnp"]
    for o, v in want.items():
        assert got[o].shape == fields[o].shape == v.shape
        np.testing.assert_array_equal(np.asarray(got[o]), np.asarray(v))


def test_overlapped_step_staggered_output_rejected(rng):
    """Outputs staggered along a decomposed axis are out of overlapped_
    step's contract (shared rank faces) — rejected before any collective."""
    from repro.distributed import overlap

    ps = init_parallel_stencil(backend="jnp", ndims=2)

    @ps.parallel(outputs=("q2",))
    def kern(q2, q, T):
        return {"q2": 0.5 * q + 0.5 * fd.av_xa(T)}

    q = _arr(rng, (SHAPE[0] - 1, SHAPE[1]))
    fields = dict(q2=q, q=q, T=_arr(rng))
    with pytest.raises(NotImplementedError, match="staggered along decomposed"):
        overlap.overlapped_step(kern, fields, {}, ("T",), ("x",))


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_unrecognized_update_extent_raises(backend, rng):
    ps = init_parallel_stencil(backend=backend, ndims=2)

    @ps.parallel(outputs=("U2",))
    def kern(U2, U):
        return {"U2": U[:-1, :]}  # neither `all` nor `inn` extent

    with pytest.raises(ValueError, match="expected"):
        kern(U2=_arr(rng), U=_arr(rng))


def test_rotation_shape_mismatch_raises(rng):
    ps = init_parallel_stencil(backend="pallas", ndims=2)

    @ps.parallel(outputs=("T2",), rotations={"T2": "q"})
    def kern(T2, T, q):
        return {"T2": fd.inn(T)}

    T, q = _arr(rng), _arr(rng, (SHAPE[0] - 1, SHAPE[1]))
    with pytest.raises(ValueError, match="different"):
        kern.run_steps(2, T2=T.copy(), T=T, q=q)


def test_partial_rotations_raise(rng):
    """Every output of a coupled system must rotate for nsteps > 1."""
    ps = init_parallel_stencil(backend="jnp", ndims=2)

    @ps.parallel(outputs=("A2", "B2"), rotations={"A2": "A"})
    def kern(A2, B2, A, B):
        return {"A2": fd.inn(A), "B2": fd.inn(B)}

    A, B = _arr(rng), _arr(rng)
    with pytest.raises(ValueError, match="rotations"):
        kern.run_steps(2, A2=A.copy(), B2=B.copy(), A=A, B=B)


def test_field_geometry_validation():
    shapes, offsets = field_geometry(
        (16, 16), ("a", "q"), {"q": (15, 16)}, radius=1)
    assert shapes["a"] == (16, 16) and offsets["q"] == (1, 0)
    with pytest.raises(ValueError, match="staggering band"):
        field_geometry((16, 16), ("q",), {"q": (13, 16)}, radius=1)
    with pytest.raises(ValueError, match="rank"):
        field_geometry((16, 16), ("q",), {"q": (16,)}, radius=1)


# --------------------------------------------------------------------------
# launch derivation / autotune keyed on the field set's footprint
# --------------------------------------------------------------------------
def test_derive_launch_sums_field_set_footprint():
    """The VMEM fit must budget the SUM of the per-field windows: a larger
    coupled system yields smaller (or equal) blocks under one budget."""
    shape = (256, 256)
    budget = 1 << 17
    _, b2 = derive_launch(shape, 1, 2, 4, vmem_budget=budget,
                          field_offsets=[(0, 0)] * 2)
    _, b6 = derive_launch(shape, 1, 6, 4, vmem_budget=budget,
                          field_offsets=[(0, 0)] * 6)
    assert np.prod(b6) <= np.prod(b2)
    window6 = 6 * np.prod([b + 2 for b in b6]) * 4
    assert window6 <= budget
    # staggered fields shave their offsets off the window accounting
    offs = [(0, 0), (1, 0), (0, 1)]
    _, blk = derive_launch(shape, 1, 3, 4, vmem_budget=budget,
                           field_offsets=offs)
    window = sum(np.prod([b + 2 - o for b, o in zip(blk, off)])
                 for off in offs) * 4
    assert window <= budget


def test_autotune_keyed_on_field_offsets(tmp_path):
    """Two systems with the same field count but different staggering must
    tune independently (different VMEM footprints)."""
    calls = []

    def make_step(tile, k):
        def run():
            calls.append((tile, k))
            return jnp.zeros(())
        return run

    kw = dict(shape=(16, 16), dtype="float32", radius=1, n_fields=3,
              nsteps_candidates=(1,), iters=1, tag="offsets-unit")
    r1 = autotune.autotune(make_step, field_offsets=[(0, 0)] * 3, **kw)
    n1 = len(calls)
    r2 = autotune.autotune(make_step,
                           field_offsets=[(0, 0), (1, 0), (0, 1)], **kw)
    assert len(calls) > n1  # re-measured, not inherited
    k1 = autotune.cache_key(**{k: v for k, v in kw.items()
                               if k not in ("iters",)},
                            field_offsets=[(0, 0)] * 3)
    k2 = autotune.cache_key(**{k: v for k, v in kw.items()
                               if k not in ("iters",)},
                            field_offsets=[(0, 0), (1, 0), (0, 1)])
    assert k1 != k2
    assert r1.nsteps == r2.nsteps == 1
