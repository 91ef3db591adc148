"""Stencil IR: symbolic footprint inference (per-field/per-axis halos,
staggered offsets, declared-radius cross-check), fused boundary
conditions (bitwise vs the core.boundary post-pass on both backends,
including temporal blocking), analytic cost models (exact flop/byte
counts) and the autotuner's pre-compile candidate pruning."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import boundary, fd2d, fd3d, init_parallel_stencil, teff
from repro.ir import (BoundaryCondition, StencilCostModel, TraceError,
                      count_flops, trace_stencil)
from repro.kernels import autotune
from repro.ir import cost as roofline

SHAPE2 = (20, 24)
SHAPE3 = (16, 12, 20)


def _arr(rng, shape=SHAPE2):
    return jnp.asarray(rng.rand(*shape), jnp.float32)


def _diffusion3(ps, **kw):
    @ps.parallel(outputs=("T2",), rotations={"T2": "T"}, **kw)
    def kern(T2, T, Ci, lam, dt, _dx, _dy, _dz):
        return {"T2": fd3d.inn(T) + dt * (lam * fd3d.inn(Ci) * (
            fd3d.d2_xi(T) * _dx ** 2 + fd3d.d2_yi(T) * _dy ** 2 +
            fd3d.d2_zi(T) * _dz ** 2))}
    return kern


SC3 = dict(lam=1.0, dt=1e-4, _dx=1.0, _dy=1.0, _dz=1.0)


# --------------------------------------------------------------------------
# footprint inference
# --------------------------------------------------------------------------
def test_diffusion3d_inferred_footprint():
    """The Fig. 1 solver without any declared radius: r=1, symmetric,
    reads {T, Ci}, writes {T2}, exchange depth only for T."""
    kern = _diffusion3(init_parallel_stencil(ndims=3))
    ir = kern.stencil_ir(T2=SHAPE3, T=SHAPE3, Ci=SHAPE3, **SC3)
    assert ir.inferred_radius == 1
    assert ir.halo == ((1, 1),) * 3
    assert ir.write_modes["T2"] == ("inn",) * 3
    assert ir.write_rings["T2"] == (1, 1, 1)
    assert set(ir.read_fields) == {"T", "Ci"}
    assert ir.field_halo["T"] == ((1, 1),) * 3
    assert ir.field_halo["Ci"] == ((0, 0),) * 3   # only fd.inn -> no halo
    assert ir.io_counts() == (2, 1)


def test_gp_fused_kernel_inferred_radius2():
    """The coupled two-frame symplectic GP update (no radius declared in
    the example anymore) infers the radius-2 footprint."""
    from examples import gross_pitaevskii as gp

    cfg = gp.GPConfig(n=12)
    grid, re, im, V = gp.init_state(cfg)
    kern = gp.make_step(grid, cfg).kernels[0]
    ir = kern.stencil_ir(re2=re, im2=im, re=re, im=im, V=V, g=cfg.g,
                         dt=0.1, _dx2=1.0, _dy2=1.0, _dz2=1.0)
    assert ir.inferred_radius == 2
    assert ir.halo == ((2, 2),) * 3
    assert ir.write_rings["re2"] == (2, 2, 2)
    assert ir.write_rings["im2"] == (2, 2, 2)
    # per-FIELD depths are finer than the scalar radius: only im is read
    # two cells past the write position (through lap(re1)); re and V one.
    assert ir.field_halo["im"] == ((2, 2),) * 3
    assert ir.field_halo["re"] == ((1, 1),) * 3
    assert ir.field_halo["V"] == ((1, 1),) * 3


def test_porosity_flux_split_staggered_offsets():
    """The flux-split kernels infer the staggered face offsets and the
    one-sided (0, 1) halos of forward differences/averages."""
    from examples import porosity_waves as pw

    cfg = pw.PorosityConfig(n=24, flux_split=True)
    grid = pw.make_grid(cfg)
    fluxes, update = pw.make_step(grid, cfg).kernels
    n = cfg.n
    ir = fluxes.stencil_ir(qx=(n - 1, n), qy=(n, n - 1), phi=(n, n),
                           Pe=(n, n))
    assert ir.offsets["qx"] == (1, 0) and ir.offsets["qy"] == (0, 1)
    assert ir.write_modes["qx"] == ("all", "all")
    assert ir.halo == ((0, 1), (0, 1))
    assert ir.inferred_radius == 1
    ir_u = update.stencil_ir(phi2=(n, n), Pe2=(n, n), phi=(n, n),
                             Pe=(n, n), qx=(n - 1, n), qy=(n, n - 1),
                             dtau=0.0)
    assert ir_u.inferred_radius == 1
    assert ir_u.write_modes["phi2"] == ("inn", "inn")


def test_asymmetric_upwind_halo_and_parity(rng):
    """A one-sided (upwind) difference infers halo (1,0)/(0,0) — the VMEM
    window shrinks — and the backends still agree."""
    def upwind(T2, T, dt):
        return {"T2": fd2d.inn(T) + dt * (T[:-2, 1:-1] - T[1:-1, 1:-1])}

    U = _arr(rng)
    outs = {}
    for backend in ("jnp", "pallas"):
        ps = init_parallel_stencil(backend=backend, ndims=2)
        k = ps.parallel(outputs=("T2",))(upwind)
        outs[backend] = np.asarray(k(T2=U, T=U, dt=1e-3))
        ir = k.stencil_ir(T2=SHAPE2, T=SHAPE2, dt=0.0)
        assert ir.halo == ((1, 0), (0, 0))
    np.testing.assert_allclose(outs["jnp"], outs["pallas"], atol=1e-6)
    # The *window* halo is max(read halo, write ring) per side: the inn
    # write ring is 1, so the window extends one cell on every side even
    # where the data footprint is shallower — without that, the update
    # expression cannot reach the seam cells of interior blocks (the
    # data footprint stays (1,0)/(0,0) and is what the halo exchange
    # uses; the window inflation is a structural placement requirement).
    ps = init_parallel_stencil(backend="pallas", ndims=2)
    k = ps.parallel(outputs=("T2",))(upwind)
    k(T2=U, T=U, dt=1e-3)
    run = next(iter(k._cache.values()))
    assert run.halo == ((1, 1), (1, 1))
    symmetric = 2 * (SHAPE2[0] + 2) * (SHAPE2[1] + 2) * 4
    assert run.window_bytes <= symmetric


def test_asymmetric_upwind_multiblock_seams(rng):
    """Regression: with more than one block per axis, seam cells whose
    update index falls outside the tight data-footprint window used to be
    silently dropped (masked valid but zero-padded). The ring-covering
    window geometry must make every tiling agree with the jnp backend."""
    def upwind(T2, T, dt):
        return {"T2": fd2d.inn(T) + dt * (T[:-2, 1:-1] - T[1:-1, 1:-1])}

    U = _arr(rng)
    ps = init_parallel_stencil(backend="jnp", ndims=2)
    want = np.asarray(ps.parallel(outputs=("T2",))(upwind)(T2=U, T=U, dt=1e-3))
    for tile in ((4, 4), (10, 8)):
        ps = init_parallel_stencil(backend="pallas", ndims=2)
        k = ps.parallel(outputs=("T2",), tile=tile)(upwind)
        got = np.asarray(k(T2=U, T=U, dt=1e-3))
        np.testing.assert_allclose(got, want, atol=1e-6)


def test_inferred_zero_halo_axis_run_steps_bitwise(rng):
    """An axis the kernel never differences costs no halo; temporal
    blocking still matches k sequential calls bit-for-bit."""
    def xonly(T2, T, dt):
        return {"T2": T[1:-1, :]
                      + dt * (T[2:, :] - 2.0 * T[1:-1, :] + T[:-2, :])}

    U = _arr(rng)
    for backend in ("jnp", "pallas"):
        ps = init_parallel_stencil(backend=backend, ndims=2)
        k = ps.parallel(outputs=("T2",), rotations={"T2": "T"})(xonly)
        ir = k.stencil_ir(T2=SHAPE2, T=SHAPE2, dt=0.0)
        assert ir.halo == ((1, 1), (0, 0))
        a, b = U.copy(), U
        for _ in range(3):
            a = k(T2=a, T=b, dt=1e-3)
            a, b = b, a
        got = np.asarray(k.run_steps(3, T2=U.copy(), T=U, dt=1e-3))
        np.testing.assert_array_equal(got, np.asarray(b))


def test_declared_radius_cross_check_raises(rng):
    ps = init_parallel_stencil(ndims=2)

    @ps.parallel(outputs=("T2",), radius=2)
    def k(T2, T, dt):
        return {"T2": fd2d.inn(T) + dt * (fd2d.d2_xi(T) + fd2d.d2_yi(T))}

    with pytest.raises(ValueError, match="declared radius=2 does not match"):
        k(T2=_arr(rng), T=_arr(rng), dt=1e-3)


def test_matching_declared_radius_accepted(rng):
    ps = init_parallel_stencil(ndims=3)
    kern = _diffusion3(ps, radius=1)
    U = _arr(rng, SHAPE3)
    kern(T2=U, T=U, Ci=U, **SC3)  # no error: inferred == declared


def test_untraceable_update_falls_back_or_raises(rng):
    def weird(T2, T, dt):
        return {"T2": jnp.maximum(fd2d.inn(T), 0.1) * (1.0 + dt)}

    U = _arr(rng)
    outs = {}
    for backend in ("jnp", "pallas"):
        ps = init_parallel_stencil(backend=backend, ndims=2)
        k = ps.parallel(outputs=("T2",), radius=1)(weird)  # legacy fallback
        outs[backend] = np.asarray(k(T2=U, T=U, dt=1e-3))
    np.testing.assert_allclose(outs["jnp"], outs["pallas"], atol=1e-6)
    ps = init_parallel_stencil(ndims=2)
    k = ps.parallel(outputs=("T2",))(weird)  # no radius to fall back on
    with pytest.raises(ValueError, match="footprint inference failed"):
        k(T2=U, T=U, dt=1e-3)


def test_trace_error_surface():
    def int_index(f, s):
        return {"T2": f["T"][0]}

    with pytest.raises(TraceError, match="unit-stride"):
        trace_stencil(int_index, {"T2": (8, 8), "T": (8, 8)}, ("T2",))

    def strided(f, s):
        return {"T2": f["T"][::2, :]}

    with pytest.raises(TraceError, match="strided"):
        trace_stencil(strided, {"T2": (8, 8), "T": (8, 8)}, ("T2",))

    def mismatch(f, s):
        return {"T2": f["T"][1:, :] + f["T"][:, 1:]}

    with pytest.raises(TraceError, match="shape mismatch"):
        trace_stencil(mismatch, {"T2": (8, 8), "T": (8, 8)}, ("T2",))


def test_staggered_interior_write_rejected_at_trace():
    """The trace takes an `inn` write on a staggered axis (ParallelStencil's
    ``@inn(Vx)`` on a face field) by widening the window's high halo to
    offset + ring, which covers every block's faces; the pinned ring of
    the legacy declared-radius geometry, whose halo cannot, rejects it."""
    from repro.kernels.stencil import write_geometry

    def inner(f, s):
        return {"q2": f["q"][1:-1, 1:-1]}

    ir = trace_stencil(inner, {"q2": (7, 8), "q": (7, 8), "T": (8, 8)},
                       ("q2",))
    assert ir.write_modes["q2"] == ("inn", "inn")
    assert ir.halo[0] == (0, 2)
    with pytest.raises(ValueError, match="staggered along axis 0"):
        write_geometry((5, 6), (7, 8), (1, 0), "q2", ring=1)


def test_staggered_output_halo_covers_block_seams(rng):
    """A staggered `all`-write output whose reads are shallower than its
    offset still needs a window wide enough to cover every block frame:
    the inferred hi halo includes the output's own staggering, so small
    tiles produce no zero-padded seam columns (regression: inferred halo
    (0,0) left block-boundary rows garbage on the pallas backend)."""
    def stag(qx, A):
        return {"qx": A[:-1, :] * 2.0}

    n, m = 16, 16
    A = _arr(rng, (n, m))
    qx0 = jnp.zeros((n - 1, m), jnp.float32)
    outs = {}
    for backend in ("jnp", "pallas"):
        ps = init_parallel_stencil(backend=backend, ndims=2)
        k = ps.parallel(outputs=("qx",), tile=(4, 16))(stag)
        ir = k.stencil_ir(qx=(n - 1, m), A=(n, m))
        assert ir.halo == ((0, 1), (0, 0))  # hi side covers the offset
        assert ir.inferred_radius == 1
        outs[backend] = np.asarray(k(qx=qx0, A=A))
    np.testing.assert_allclose(outs["jnp"], outs["pallas"], atol=1e-6)
    np.testing.assert_allclose(outs["jnp"], 2.0 * np.asarray(A)[:-1, :],
                               atol=1e-6)


# --------------------------------------------------------------------------
# fused boundary conditions
# --------------------------------------------------------------------------
_BC_CASES = [
    ("dirichlet", BoundaryCondition("dirichlet", value=0.5),
     lambda a: boundary.dirichlet(a, 0.5)),
    ("neumann0", BoundaryCondition("neumann0"), boundary.neumann0),
    ("periodic", BoundaryCondition("periodic"), boundary.periodic),
    ("neumann0_d2", BoundaryCondition("neumann0", depth=2),
     lambda a: boundary.neumann0(a, depth=2)),
    ("dirichlet_ax0", BoundaryCondition("dirichlet", value=1.5, axes=(0,)),
     lambda a: boundary.dirichlet(a, 1.5, axes=(0,))),
]


def _bc_kernel(ps, bcobj=None):
    kw = {} if bcobj is None else {"bc": {"U2": bcobj}}
    @ps.parallel(outputs=("U2",), rotations={"U2": "U"}, **kw)
    def kern(U2, U, dt):
        return {"U2": fd2d.inn(U) + dt * (fd2d.d2_xi(U) + fd2d.d2_yi(U))}
    return kern


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
@pytest.mark.parametrize("case", _BC_CASES, ids=[c[0] for c in _BC_CASES])
def test_fused_bc_bitwise_equals_postpass(backend, case, rng):
    """Engine-fused boundary conditions == kernel-without-bc followed by
    the core.boundary post-pass, bit for bit, on both backends."""
    _, bcobj, post = case
    U = _arr(rng)
    ps = init_parallel_stencil(backend=backend, ndims=2)
    got = np.asarray(_bc_kernel(ps, bcobj)(U2=U, U=U, dt=1e-3))
    want = np.asarray(post(_bc_kernel(ps)(U2=U, U=U, dt=1e-3)))
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
@pytest.mark.parametrize("case", _BC_CASES, ids=[c[0] for c in _BC_CASES])
def test_fused_bc_run_steps_bitwise(backend, case, rng):
    """BCs compose with nsteps=k temporal blocking: the fused k-step path
    (in-kernel BC between sweeps; sequential-launch fallback for
    periodic) == k sequential bc-steps, bit for bit."""
    _, bcobj, _ = case
    U = _arr(rng)
    ps = init_parallel_stencil(backend=backend, ndims=2)
    kern = _bc_kernel(ps, bcobj)
    a, b = U.copy(), U
    for _ in range(3):
        a = kern(U2=a, U=b, dt=1e-3)
        a, b = b, a
    got = np.asarray(kern.run_steps(3, U2=U.copy(), U=U, dt=1e-3))
    np.testing.assert_array_equal(got, np.asarray(b))


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_fused_bc_radius2_depth1(backend, rng):
    """BC depth smaller than the write ring: the outermost layer takes
    the condition, the rest of the unwritten ring keeps prev values —
    exactly the post-pass semantics."""
    def k2(U2, U, dt):
        d4 = (-U[4:, 2:-2] + 16 * U[3:-1, 2:-2] - 30 * U[2:-2, 2:-2]
              + 16 * U[1:-3, 2:-2] - U[:-4, 2:-2]) / 12.0
        d2 = U[2:-2, 3:-1] - 2 * U[2:-2, 2:-2] + U[2:-2, 1:-3]
        return {"U2": U[2:-2, 2:-2] + dt * (d4 + d2)}

    U = _arr(rng)
    ps = init_parallel_stencil(backend=backend, ndims=2)
    kb = ps.parallel(outputs=("U2",), bc={"U2": BoundaryCondition("neumann0")})(k2)
    kr = ps.parallel(outputs=("U2",))(k2)
    got = np.asarray(kb(U2=U, U=U, dt=1e-3))
    want = np.asarray(boundary.neumann0(kr(U2=U, U=U, dt=1e-3)))
    np.testing.assert_array_equal(got, want)


def test_bc_validation_errors(rng):
    ps = init_parallel_stencil(ndims=2)
    with pytest.raises(ValueError, match="not an output"):
        _ = ps.parallel(outputs=("U2",), bc={"U": BoundaryCondition("neumann0")})(
            lambda U2, U: {"U2": 2.0 * U})
    with pytest.raises(ValueError, match="must be one of"):
        BoundaryCondition("mirror")
    k = ps.parallel(outputs=("U2",),
                    bc={"U2": BoundaryCondition("neumann0", depth=4)})(
        lambda U2, U: {"U2": 2.0 * U})
    small = jnp.zeros((6, 6), jnp.float32)
    with pytest.raises(ValueError, match="smaller than"):
        k(U2=small, U=small)


# --------------------------------------------------------------------------
# analytic cost models
# --------------------------------------------------------------------------
def test_diffusion_flop_count_exact():
    """Fig. 1 diffusion: 18 flops per interior point (9 adds + 9 muls),
    shared subexpressions counted once."""
    kern = _diffusion3(init_parallel_stencil(ndims=3))
    cost = kern.cost_model(T2=SHAPE3, T=SHAPE3, Ci=SHAPE3, **SC3)
    n_int = np.prod([s - 2 for s in SHAPE3])
    assert cost.flops.adds == 9 * n_int
    assert cost.flops.muls == 9 * n_int
    assert cost.flops.total() == 18 * n_int


def test_a_eff_from_ir_matches_hand_count():
    """The IR-derived A_eff reproduces the paper's hand count for the
    diffusion solver (2 reads + 1 write) and divides by k when blocked."""
    kern = _diffusion3(init_parallel_stencil(ndims=3))
    ir = kern.stencil_ir(T2=SHAPE3, T=SHAPE3, Ci=SHAPE3, **SC3)
    n = int(np.prod(SHAPE3))
    hand = teff.a_eff(n, n_read=2, n_write=1, itemsize=4)
    assert teff.a_eff_from_ir(ir, itemsize=4) == hand
    assert teff.a_eff_from_ir(ir, itemsize=4, nsteps=4) == hand / 4
    assert teff.io_counts_from_ir(ir) == (2, 1)


def test_shared_subexpression_counted_once():
    def kern(f, s):
        c = f["T"][1:-1, 1:-1]
        return {"T2": (c + c * c) + 0.0 * c}

    ir = trace_stencil(kern, {"T2": (10, 10), "T": (10, 10)}, ("T2",))
    fc = count_flops(ir.exprs)
    n = 8 * 8
    # c*c (mul), 0.0*c (mul), two adds — the slice node c counted once, free
    assert fc.muls == 2 * n and fc.adds == 2 * n


def test_cost_model_roofline_position():
    kern = _diffusion3(init_parallel_stencil(ndims=3))
    cost = kern.cost_model(T2=SHAPE3, T=SHAPE3, Ci=SHAPE3, **SC3)
    rec = roofline.stencil_roofline(cost, nsteps=1, hw=teff.TPU_V5E)
    assert rec["dominant"] == "memory"   # stencils sit far left of ridge
    assert rec["intensity_flop_per_byte"] < rec["ridge_flop_per_byte"]
    assert rec["bytes_per_step"] == cost.read_bytes + cost.write_bytes
    rec4 = roofline.stencil_roofline(cost, nsteps=4, hw=teff.TPU_V5E)
    assert rec4["bytes_per_step"] == rec["bytes_per_step"] / 4


def test_cost_model_predicts_halo_overhead():
    cost = StencilCostModel(
        shape=(64, 64), itemsize=4, flops=count_flops({}),
        read_bytes=64 * 64 * 4 * 2, write_bytes=64 * 64 * 4,
        halo=((1, 1), (1, 1)), field_offsets=((0, 0), (0, 0)))
    # deeper blocking on smaller tiles fetches relatively more halo
    f_big = cost.fetched_bytes_per_step((64, 64), 1)
    f_small_k4 = cost.fetched_bytes_per_step((8, 8), 4)
    per_point_big = f_big / (64 * 64)
    per_point_small = f_small_k4 / (64 * 64)
    assert per_point_small > per_point_big / 4  # halo overhead eats the /k


# --------------------------------------------------------------------------
# autotune pruning via the analytic model
# --------------------------------------------------------------------------
def test_autotune_prunes_candidates_before_compiling():
    """With a cost model + hardware spec, predicted-slow (tile, k)
    configs are dropped before make_step is ever called for them."""
    built = []

    def make_step(tile, k):
        built.append((tuple(tile), k))
        return lambda: jnp.zeros(())

    shape = (64, 64)
    # 3 fields, radius-1 symmetric halo: tiny tiles fetch ~2x the bytes
    cost = StencilCostModel(
        shape=shape, itemsize=4, flops=count_flops({}),
        read_bytes=2 * 64 * 64 * 4, write_bytes=64 * 64 * 4,
        halo=((1, 1), (1, 1)),
        field_offsets=((0, 0), (0, 0), (0, 0)))
    tiles = [(64, 64), (2, 64), (2, 2)]
    r = autotune.autotune(
        make_step, shape=shape, dtype="float32", radius=1, n_fields=3,
        nsteps_candidates=(1,), tiles=tiles, iters=1, tag="prune-unit",
        cost_model=cost, hw=teff.TPU_V5E, prune_ratio=1.2)
    assert r.candidates_pruned >= 1
    assert len(built) == 3 - r.candidates_pruned  # pruned: never built
    assert (2, 2) not in [t for t, _ in built]    # the worst tile never ran
    assert r.tile == (64, 64)


def test_autotune_prune_key_distinct_from_unpruned():
    k1 = autotune.cache_key((8, 8), "float32", 1, 3, "t", (1,))
    k2 = autotune.cache_key((8, 8), "float32", 1, 3, "t", (1,),
                            prune=("TPU v5e", 2.0))
    assert k1 != k2


# --------------------------------------------------------------------------
# exchange-depth tightening hooks
# --------------------------------------------------------------------------
def test_field_halo_drives_exchange_depths():
    """Fields the kernel reads shallowly (or not at all) get shallower
    (or zero) exchange depths — the IR data the distributed layer uses."""
    kern = _diffusion3(init_parallel_stencil(ndims=3))
    ir = kern.stencil_ir(T2=SHAPE3, T=SHAPE3, Ci=SHAPE3, **SC3)
    assert ir.field_halo["T"] == ((1, 1),) * 3
    assert ir.field_halo["Ci"] == ((0, 0),) * 3
    assert ir.field_halo["T2"] == ((0, 0),) * 3  # outputs are write-only
