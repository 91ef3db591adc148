"""Fused in-launch reduction epilogues + device-resident iteration.

Tolerance contract (the reassociation rule): a reduction's value is
bitwise-reproducible only WITHIN one compiled program. jnp-vs-pallas,
fused-vs-post-pass and fused-vs-host-loop comparisons are two separately
compiled programs that fold in different orders (and contract FMAs
differently), so every cross-program assertion here is ``allclose``
(atol ~1e-6 / small rtol), never equality.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import run_subprocess
from repro.core import fd2d, fd3d, init_parallel_stencil, iterate, teff
from repro.ir import Reduction

ALL_REDS = {"err": "max_abs_diff(T2, T)", "mx": "max_abs(T2)",
            "s": "sum(T2)", "m2": "sum_sq(T2)"}


def diffusion_kernel(backend, reductions=ALL_REDS, tile=None, bc=None,
                     march_axis=None):
    ps = init_parallel_stencil(backend=backend, ndims=3)

    @ps.parallel(outputs=("T2",), rotations={"T2": "T"}, tile=tile, bc=bc,
                 march_axis=march_axis, reductions=reductions)
    def kern(T2, T, Ci, lam, dt, _dx, _dy, _dz):
        return {"T2": fd3d.inn(T) + dt * (lam * fd3d.inn(Ci) * (
            fd3d.d2_xi(T) * _dx ** 2 + fd3d.d2_yi(T) * _dy ** 2 +
            fd3d.d2_zi(T) * _dz ** 2))}

    return kern


def setup3d(rng, shape=(16, 16, 16)):
    T = jnp.asarray(rng.rand(*shape), jnp.float32)
    Ci = jnp.asarray(rng.rand(*shape) + 0.5, jnp.float32)
    sc = dict(lam=1.0, dt=1e-3, _dx=1.0, _dy=1.0, _dz=1.0)
    return T, Ci, sc


def post_pass(out, T):
    """The separate-norm-pass reference for ALL_REDS."""
    return {"err": jnp.max(jnp.abs(out - T)), "mx": jnp.max(jnp.abs(out)),
            "s": jnp.sum(out), "m2": jnp.sum(out ** 2)}


def assert_reds_close(got, want, rtol=1e-4):
    assert set(got) == set(want)
    for n in want:
        np.testing.assert_allclose(float(got[n]), float(want[n]), rtol=rtol,
                                   err_msg=n)


# ---------------------------------------------------------------- spec layer
def test_reduction_spec_validation():
    with pytest.raises(ValueError, match="must be one of"):
        Reduction("l7_norm", "T2")
    with pytest.raises(ValueError, match="two operands"):
        Reduction("max_abs_diff", "T2")
    with pytest.raises(ValueError, match="one operand"):
        Reduction("sum", "T2", "T")
    r = Reduction("max_abs_diff", "T2", "T")
    assert r.operands == ("T2", "T") and r.combine == "max"
    assert Reduction("sum_sq", "psi").combine == "sum"


def test_reduction_string_parsing(rng):
    # compact string form == explicit dataclass form
    T, Ci, sc = setup3d(rng)
    ka = diffusion_kernel("jnp", {"err": "max_abs_diff(T2, T)"})
    kb = diffusion_kernel("jnp", {"err": Reduction("max_abs_diff",
                                                   "T2", "T")})
    _, ra = ka(T2=T, T=T, Ci=Ci, **sc)
    _, rb = kb(T2=T, T=T, Ci=Ci, **sc)
    assert float(ra["err"]) == float(rb["err"])
    with pytest.raises(ValueError, match="cannot parse"):
        diffusion_kernel("jnp", {"err": "max_abs_diff"})(
            T2=T, T=T, Ci=Ci, **sc)


def test_unknown_operand_rejected(rng):
    T, Ci, sc = setup3d(rng)
    kern = diffusion_kernel("jnp", {"err": "max_abs(Q)"})
    with pytest.raises(ValueError, match="not a field"):
        kern(T2=T, T=T, Ci=Ci, **sc)


def test_periodic_bc_incompatible():
    from repro.ir import BoundaryCondition
    with pytest.raises(ValueError, match="periodic"):
        diffusion_kernel("jnp", bc={"T2": BoundaryCondition("periodic")})


# ------------------------------------------------------------ backend parity
def test_jnp_fused_equals_post_pass(rng):
    T, Ci, sc = setup3d(rng)
    kern = diffusion_kernel("jnp")
    out, reds = kern(T2=T, T=T, Ci=Ci, **sc)
    assert_reds_close(reds, post_pass(out, T), rtol=1e-6)


def test_pallas_fused_vs_jnp_and_post_pass(rng):
    T, Ci, sc = setup3d(rng)
    out_j, reds_j = diffusion_kernel("jnp")(T2=T, T=T, Ci=Ci, **sc)
    out_p, reds_p = diffusion_kernel("pallas")(T2=T, T=T, Ci=Ci, **sc)
    np.testing.assert_allclose(np.asarray(out_p), np.asarray(out_j),
                               atol=1e-6)
    assert_reds_close(reds_p, reds_j)
    assert_reds_close(reds_p, post_pass(out_j, T))


def test_pallas_multiblock_partials(rng):
    # several grid tiles per axis: the per-tile partials must tile the
    # whole-array fold without overlap or holes
    T, Ci, sc = setup3d(rng, shape=(16, 16, 16))
    kern = diffusion_kernel("pallas", tile=(4, 8, 16))
    out, reds = kern(T2=T, T=T, Ci=Ci, **sc)
    assert kern.launch_info  # compiled
    (info,) = kern.launch_info.values()
    assert info["grid"] == (4, 2, 1)
    assert_reds_close(reds, post_pass(out, T))


def test_bc_applied_before_reduction(rng):
    # a dirichlet ring pins T2's faces to 0, so max_abs(T2) must see the
    # POST-bc values — fused path == post-pass on the bc'd output
    from repro.ir import BoundaryCondition
    T, Ci, sc = setup3d(rng)
    bc = {"T2": BoundaryCondition("dirichlet", value=0.0)}
    for backend in ("jnp", "pallas"):
        kern = diffusion_kernel(backend, bc=bc)
        out, reds = kern(T2=T, T=T, Ci=Ci, **sc)
        assert_reds_close(reds, post_pass(out, T))


def test_run_steps_reduces_final_sweep_only(rng):
    # k-fused launch's reduction == the check a sequential k-step loop
    # computes after its LAST step (diff of step k vs step k-1)
    T, Ci, sc = setup3d(rng)
    for backend in ("jnp", "pallas"):
        kern = diffusion_kernel(backend)
        plain = kern.with_reductions(None)
        cur = dict(T2=T, T=T)
        for _ in range(3):
            prev = cur["T"]
            out = plain(T2=cur["T2"], T=cur["T"], Ci=Ci, **sc)
            cur = dict(T2=prev, T=out)
        want = post_pass(cur["T"], cur["T2"])
        outk, redk = kern.run_steps(3, T2=T, T=T, Ci=Ci, **sc)
        np.testing.assert_allclose(np.asarray(outk), np.asarray(cur["T"]),
                                   atol=1e-6)
        assert_reds_close(redk, want)


# ------------------------------------------------------------ streamed path
@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_march_reduction_parity(backend, rng):
    # the lagged partials of the sequential march grid (priming writes
    # overwritten, drain flushing the tail) must equal the all-parallel
    # fold
    T, Ci, sc = setup3d(rng, shape=(24, 16, 16))
    # the tile pins the all-parallel reference (the default streams)
    kern = diffusion_kernel(backend, tile=(4, 16, 16))
    out_ref, reds_ref = kern(T2=T, T=T, Ci=Ci, **sc)
    marched = kern.marched(0)
    out_m, reds_m = marched(T2=T, T=T, Ci=Ci, **sc)
    if backend == "pallas":
        (info,) = (v for v in marched._cache.values())
        assert info.march_axis == 0 and not info.march_fallback
    np.testing.assert_allclose(np.asarray(out_m), np.asarray(out_ref),
                               atol=1e-6)
    assert_reds_close(reds_m, reds_ref)


def test_march_ksteps_reduction_parity(rng):
    T, Ci, sc = setup3d(rng, shape=(24, 16, 16))
    for backend in ("jnp", "pallas"):
        kern = diffusion_kernel(backend, tile=(4, 16, 16))
        _, reds_ref = kern.run_steps(2, T2=T, T=T, Ci=Ci, **sc)
        out, reds = kern.marched(0).run_steps(2, T2=T, T=T, Ci=Ci, **sc)
        assert_reds_close(reds, reds_ref)


# ---------------------------------------------------------- coupled systems
def porosity_kernel(backend, reductions):
    ps = init_parallel_stencil(backend=backend, ndims=2)

    @ps.parallel(outputs=("phi2", "Pe2"),
                 rotations={"phi2": "phi", "Pe2": "Pe"},
                 reductions=reductions)
    def update(phi2, Pe2, phi, Pe, dtau):
        k = (phi / 0.01) ** 3
        qx = -fd2d.av_xa(k) * fd2d.d_xa(Pe)
        qy = -fd2d.av_ya(k) * (fd2d.d_ya(Pe) - 30.0 * (fd2d.av_ya(phi)
                                                       - 0.01))
        div_q = fd2d.d_xa(qx[:, 1:-1]) + fd2d.d_ya(qy[1:-1, :])
        Pe_new = fd2d.inn(Pe) + dtau * (-(div_q + fd2d.inn(Pe)))
        phi_new = fd2d.inn(phi) + dtau * (-(1.0 - fd2d.inn(phi)) * Pe_new)
        return {"phi2": phi_new, "Pe2": Pe_new}

    return update


def test_coupled_per_output_reductions(rng):
    # per-output reductions on a coupled system: residual on Pe, bounds
    # on phi, in ONE launch, both backends
    n = 24
    phi = jnp.asarray(0.01 * (1 + 0.1 * rng.rand(n, n)), jnp.float32)
    Pe = jnp.asarray(0.01 * rng.rand(n, n), jnp.float32)
    reds = {"err": "max_abs_diff(Pe2, Pe)", "phimax": "max_abs(phi2)",
            "mass": "sum(phi2)"}
    outs_j, reds_j = porosity_kernel("jnp", reds)(
        phi2=phi, Pe2=Pe, phi=phi, Pe=Pe, dtau=1e-4)
    want = {"err": jnp.max(jnp.abs(outs_j["Pe2"] - Pe)),
            "phimax": jnp.max(jnp.abs(outs_j["phi2"])),
            "mass": jnp.sum(outs_j["phi2"])}
    assert_reds_close(reds_j, want, rtol=1e-5)
    outs_p, reds_p = porosity_kernel("pallas", reds)(
        phi2=phi, Pe2=Pe, phi=phi, Pe=Pe, dtau=1e-4)
    for o in outs_j:
        np.testing.assert_allclose(np.asarray(outs_p[o]),
                                   np.asarray(outs_j[o]), atol=1e-6)
    assert_reds_close(reds_p, reds_j)


def test_staggered_operand_rejected(rng):
    # each operand reduces over its own extent, so a reduction over two
    # operands of different staggerings is a pointed error
    n = 16
    ps = init_parallel_stencil(backend="jnp", ndims=2)

    @ps.parallel(outputs=("qx",), reductions={"q": "max_abs_diff(qx, Pe)"})
    def fluxes(qx, Pe):
        return {"qx": -fd2d.d_xa(Pe)}

    Pe = jnp.asarray(np.random.RandomState(0).rand(n, n), jnp.float32)
    qx = jnp.zeros((n - 1, n), jnp.float32)
    with pytest.raises(ValueError, match="staggered"):
        fluxes(qx=qx, Pe=Pe)


STAGGERED_REDS = {"s": "sum(Rx)", "m2": "sum_sq(Rx)", "mx": "max_abs(Rx)",
                  "p": "sum(Pt)"}


@pytest.mark.parametrize("backend,tile", [
    ("jnp", None), ("pallas", None), ("pallas", (4, 15, 15))])
def test_staggered_output_reduces_over_own_extent(backend, tile):
    """A reduction over a staggered output (the Stokes residual's
    (n-1, n-2, n-2) beside the (n+1, n, n) face field) equals the plain
    reduction over the output's own extent: the base-extent pad it is
    stored with never enters, on one block or on tiled blocks."""
    n = 15
    rng = np.random.RandomState(3)
    ps = init_parallel_stencil(backend=backend, ndims=3)

    @ps.parallel(outputs=("Rx", "Pt"), tile=tile, reductions=STAGGERED_REDS)
    def resid(Rx, Pt, Vx, tau, dx):
        return {"Rx": fd3d.d_xi(tau) / dx + fd3d.av_xi(Pt),
                "Pt": Pt - 0.5 * fd3d.d_xa(Vx)}

    def arr(*shape):
        return jnp.asarray(rng.rand(*shape) + 1.0, jnp.float32)

    Vx, tau, Pt = arr(n + 1, n, n), arr(n, n, n), arr(n, n, n)
    outs, reds = resid(Rx=jnp.full((n - 1, n - 2, n - 2), 1e3, jnp.float32),
                       Pt=Pt, Vx=Vx, tau=tau, dx=0.5)
    Rx, Pt2 = outs["Rx"], outs["Pt"]
    assert Rx.shape == (n - 1, n - 2, n - 2) and Pt2.shape == (n, n, n)
    want = {"s": jnp.sum(Rx), "m2": jnp.sum(Rx ** 2),
            "mx": jnp.max(jnp.abs(Rx)), "p": jnp.sum(Pt2)}
    assert_reds_close(reds, want, rtol=1e-5)


# ------------------------------------------------------------ IR accounting
def test_ir_and_cost_accounting(rng):
    T, Ci, sc = setup3d(rng)
    kern = diffusion_kernel("jnp", {"err": "max_abs_diff(T2, T)"})
    shape = tuple(T.shape)
    ir = kern.stencil_ir(T2=shape, T=shape, Ci=shape, **sc)
    assert set(ir.reductions) == {"err"}
    assert ir.check_read_fields == ("T2", "T")
    assert ir.check_io_bytes(4) == 2 * T.size * 4
    assert "max_abs_diff(T2, T)" in ir.describe()
    # the traced check expression: |T2 - T| = one sub + one abs per
    # element, plus the fold's combine op
    cost = kern.cost_model(T2=shape, T=shape, Ci=shape, **sc)
    assert cost.n_reductions == 1
    assert cost.check_read_bytes == ir.check_io_bytes(4)
    assert cost.check_flops.adds == 3 * T.size
    # separate check pass re-reads both operands; fused pays one partial
    # per tile
    tile = (8, 8, 16)
    sep = cost.check_bytes_per_step(check_every=4, fused=False)
    assert sep == ir.check_io_bytes(4) / 4
    fused = cost.check_bytes_per_step(check_every=4, fused=True, tile=tile)
    assert 0 < fused <= (2 * 2 * 1) * 4 / 4
    assert cost.fetched_bytes_per_step(tile, 1, check_every=4,
                                       fused_checks=False) == \
        cost.fetched_bytes_per_step(tile, 1) + sep
    # teff-level helper mirrors the same accounting
    a = teff.a_eff(T.size, 2, 1, 4)
    assert teff.a_eff_checked(a, ir.check_io_bytes(4), 4, fused=True) == a
    assert teff.a_eff_checked(a, ir.check_io_bytes(4), 4, fused=False) == \
        a + ir.check_io_bytes(4) / 4


def test_plain_kernel_has_no_check_accounting(rng):
    T, Ci, sc = setup3d(rng)
    kern = diffusion_kernel("jnp", reductions=None)
    shape = tuple(T.shape)
    ir = kern.stencil_ir(T2=shape, T=shape, Ci=shape, **sc)
    assert ir.reductions == {} and ir.check_io_bytes(4) == 0
    cost = kern.cost_model(T2=shape, T=shape, Ci=shape, **sc)
    assert cost.check_bytes_per_step(1, fused=False) == 0.0


def test_with_reductions_variants_memoized(rng):
    kern = diffusion_kernel("jnp")
    plain = kern.with_reductions(None)
    assert plain.reductions == {}
    assert kern.with_reductions(None) is plain
    assert plain.with_reductions(ALL_REDS).reductions == kern.reductions
    assert kern.with_reductions(ALL_REDS) is kern
    # marched variants carry the reduction set along
    assert kern.marched(1).reductions == kern.reductions


# ------------------------------------------------- device-resident iteration
def test_solve_until_matches_host_loop(rng):
    T, Ci, sc = setup3d(rng, shape=(12, 12, 12))
    sc = dict(sc, dt=0.05)  # near the stability limit: fast decay
    kern = diffusion_kernel("jnp", {"err": "max_abs_diff(T2, T)"})
    res = iterate.solve_until(kern, dict(T2=T, T=T, Ci=Ci), sc, tol=2e-5,
                              max_iters=400, check_every=5)
    plain = kern.with_reductions(None)
    cur, it, err = dict(T2=T, T=T), 0, np.inf
    while err > 2e-5 and it < 400:
        for _ in range(5):
            out = plain(T2=cur["T2"], T=cur["T"], Ci=Ci, **sc)
            cur["T2"], cur["T"] = cur["T"], out
            it += 1
        err = float(jnp.max(jnp.abs(cur["T"] - cur["T2"])))
    assert 0 < it < 400, "host loop should converge before the cap"
    assert int(res.iters) == it
    np.testing.assert_allclose(float(res.err), err, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(res.fields["T"]),
                               np.asarray(cur["T"]), atol=1e-6)
    np.testing.assert_allclose(np.asarray(res.output(kern)),
                               np.asarray(cur["T"]), atol=1e-6)


def test_solve_until_pallas_backend(rng):
    T, Ci, sc = setup3d(rng, shape=(12, 12, 12))
    kj = diffusion_kernel("jnp", {"err": "max_abs_diff(T2, T)"})
    kp = diffusion_kernel("pallas", {"err": "max_abs_diff(T2, T)"})
    rj = iterate.solve_until(kj, dict(T2=T, T=T, Ci=Ci), sc, tol=5e-5,
                             max_iters=200, check_every=10)
    rp = iterate.solve_until(kp, dict(T2=T, T=T, Ci=Ci), sc, tol=5e-5,
                             max_iters=200, check_every=10)
    assert int(rp.iters) == int(rj.iters)
    np.testing.assert_allclose(np.asarray(rp.fields["T"]),
                               np.asarray(rj.fields["T"]), atol=1e-5)


def test_solve_until_until_above(rng):
    # drift-guard polarity: iterate while the monitored value stays UNDER
    # tol; the growing sum_sq of an unstable-dt diffusion trips it
    T, Ci, sc = setup3d(rng, shape=(10, 10, 10))
    kern = diffusion_kernel("jnp", {"m": "sum_sq(T2)"})
    m0 = float(jnp.sum(T ** 2))
    res = iterate.solve_until(
        kern, dict(T2=T, T=T, Ci=Ci), sc, tol=1e-12, max_iters=50,
        check_every=5, error=lambda r: jnp.abs(r["m"] - m0) / m0,
        until="above")
    assert int(res.iters) == 5  # first check already exceeds a 1e-12 drift
    assert float(res.err) > 1e-12


def test_solve_until_zero_host_transfers():
    # trace assertion: the whole solve is ONE lax.while_loop — no eqn in
    # the driver's jaxpr moves data to the host between checks
    rng = np.random.RandomState(0)
    T = jnp.asarray(rng.rand(10, 10, 10), jnp.float32)
    Ci = jnp.ones_like(T)
    sc = dict(lam=1.0, dt=1e-3, _dx=1.0, _dy=1.0, _dz=1.0)
    kern = diffusion_kernel("jnp", {"err": "max_abs_diff(T2, T)"})
    solver = iterate.make_solver(kern, sc, check_every=3)
    jaxpr = jax.make_jaxpr(solver)(dict(T2=T, T=T, Ci=Ci), 1e-5, 100)
    names = [e.primitive.name for e in jaxpr.eqns]
    assert names.count("while") == 1
    forbidden = {"io_callback", "pure_callback", "device_put",
                 "debug_callback"}
    all_names = set(names)
    for e in jaxpr.eqns:
        for sub in e.params.values():
            if hasattr(sub, "jaxpr"):
                all_names |= {q.primitive.name for q in sub.jaxpr.eqns}
    assert not (all_names & forbidden)


def test_single_kernel_solver_traces_as_before(rng):
    """One kernel, or a sequence holding only it, traces to one ``while``
    whose body holds ``check_every`` launches of that kernel, as before
    sequences of launches were taken."""
    T, Ci, sc = setup3d(rng, shape=(10, 10, 10))
    kern = diffusion_kernel("pallas", {"err": "max_abs_diff(T2, T)"})
    args = (dict(T2=T, T=T, Ci=Ci), 1e-5, 100)
    jaxpr = jax.make_jaxpr(iterate.make_solver(kern, sc, check_every=3))(
        *args)
    (loop,) = [e for e in jaxpr.eqns if e.primitive.name == "while"]
    calls = [e.params["name"] for e in loop.params["body_jaxpr"].jaxpr.eqns
             if e.primitive.name == "pallas_call"]
    assert calls == ["kern"] * 3
    seq = jax.make_jaxpr(iterate.make_solver((kern,), sc, check_every=3))(
        *args)
    assert str(seq) == str(jaxpr)


def test_launch_sequence_errors(rng):
    """A sequence of launches: reductions are merged by name (a name
    declared twice is refused), and at least one launch must declare
    one."""
    T, Ci, sc = setup3d(rng, shape=(8, 8, 8))
    a = diffusion_kernel("jnp", {"err": "max_abs(T2)"})
    b = diffusion_kernel("jnp", {"err": "sum(T2)"})
    with pytest.raises(ValueError, match="declared by two launches"):
        iterate.make_solver((a, b), sc)
    with pytest.raises(ValueError, match="fused reductions"):
        iterate.make_solver((a.with_reductions(None),
                             b.with_reductions(None)), sc)
    with pytest.raises(ValueError, match="at least one kernel"):
        iterate.make_solver((), sc)


def test_launch_sequence_rebinds_outputs(rng):
    """In a sequence, an output without a rotation rebinds its own name
    for the launches after it; a rotated one swaps with its partner. Two
    half-step launches then equal the host loop that chains them."""
    T, Ci, sc = setup3d(rng, shape=(10, 10, 10))
    ps = init_parallel_stencil(backend="jnp", ndims=3)

    @ps.parallel(outputs=("U",))
    def half(U, T, dt):
        return {"U": fd3d.inn(T) + 0.5 * dt * fd3d.d2_xi(T)}

    @ps.parallel(outputs=("T2",), rotations={"T2": "T"},
                 reductions={"err": "max_abs_diff(T2, T)"})
    def rest(T2, T, U, dt):
        return {"T2": fd3d.inn(U) + 0.5 * dt * fd3d.d2_xi(U)}

    res = iterate.solve_until((half, rest), dict(U=T, T2=T, T=T),
                              dict(dt=0.1), tol=0.0, max_iters=6,
                              check_every=3)
    cur = dict(U=T, T2=T, T=T)
    for _ in range(6):
        cur["U"] = half(U=cur["U"], T=cur["T"], dt=0.1)
        out, _ = rest(T2=cur["T2"], T=cur["T"], U=cur["U"], dt=0.1)
        cur["T2"], cur["T"] = cur["T"], out
    assert int(res.iters) == 6
    for k in ("U", "T", "T2"):
        np.testing.assert_allclose(np.asarray(res.fields[k]),
                                   np.asarray(cur[k]), rtol=0, atol=1e-6)
    np.testing.assert_allclose(
        float(res.err), float(jnp.max(jnp.abs(cur["T"] - cur["T2"]))),
        rtol=1e-6)


def test_solve_until_errors(rng):
    T, Ci, sc = setup3d(rng, shape=(8, 8, 8))
    plain = diffusion_kernel("jnp", reductions=None)
    with pytest.raises(ValueError, match="fused reductions"):
        iterate.solve_until(plain, dict(T2=T, T=T, Ci=Ci), sc, tol=1e-5,
                            max_iters=10)
    kern = diffusion_kernel("jnp")
    with pytest.raises(ValueError, match="error="):
        iterate.solve_until(kern, dict(T2=T, T=T, Ci=Ci), sc, tol=1e-5,
                            max_iters=10)  # 4 reductions, ambiguous
    with pytest.raises(ValueError, match="not a declared reduction"):
        iterate.solve_until(kern, dict(T2=T, T=T, Ci=Ci), sc, tol=1e-5,
                            max_iters=10, error="nope")
    with pytest.raises(ValueError, match="until"):
        iterate.solve_until(kern, dict(T2=T, T=T, Ci=Ci), sc, tol=1e-5,
                            max_iters=10, error="err", until="sideways")
    with pytest.raises(ValueError, match="check_every"):
        iterate.solve_until(kern, dict(T2=T, T=T, Ci=Ci), sc, tol=1e-5,
                            max_iters=10, error="err", check_every=0)
    # an output without a rotation is not an error: it is written fresh
    # under its own name each iteration, for a lone kernel as in a sequence
    ps = init_parallel_stencil(backend="jnp", ndims=3)

    @ps.parallel(outputs=("T2",), reductions={"err": "max_abs(T2)"})
    def norot(T2, T, dt):
        return {"T2": fd3d.inn(T) * dt}

    res = iterate.solve_until(norot, dict(T2=T, T=T), dict(dt=0.5), tol=1e-5,
                              max_iters=10)
    once, _ = norot(T2=T, T=T, dt=0.5)
    assert int(res.iters) == 10
    np.testing.assert_array_equal(np.asarray(res.fields["T2"]),
                                  np.asarray(once))
    np.testing.assert_array_equal(np.asarray(res.fields["T"]), np.asarray(T))


# ------------------------------------------------------------- example wiring
def test_porosity_tol_mode_matches_fixed_steps():
    from examples import porosity_waves as pw

    # tol small enough that the cap binds: --tol must reproduce the
    # plain nt-step run exactly (same kernel, same rotation order)
    base = pw.PorosityConfig(n=24, nt=30)
    r_fix = pw.solve(base)
    r_tol = pw.solve(pw.PorosityConfig(n=24, nt=30, tol=1e-12,
                                       check_every=10))
    assert r_tol["iters"] == 30
    np.testing.assert_allclose(np.asarray(r_tol["phi"]),
                               np.asarray(r_fix["phi"]), atol=1e-6)
    # a loose tol stops early, at a check boundary
    r_loose = pw.solve(pw.PorosityConfig(n=24, nt=300, tol=1e-3,
                                         check_every=5))
    assert r_loose["iters"] < 300 and r_loose["iters"] % 5 == 0
    assert r_loose["residual"] < 1e-3


def test_porosity_tol_mode_rejects_flux_split_and_periodic():
    from examples import porosity_waves as pw

    with pytest.raises(ValueError, match="flux-split"):
        pw.solve(pw.PorosityConfig(n=24, nt=10, tol=1e-3, flux_split=True))
    with pytest.raises(ValueError, match="periodic"):
        pw.solve(pw.PorosityConfig(n=24, nt=10, tol=1e-3, bc="periodic"))


def test_gp_drift_guard():
    from examples import gross_pitaevskii as gp

    # generous tol: runs to the cap, drift equals the plain solve's
    r_fix = gp.solve(gp.GPConfig(n=12, nt=20))
    r = gp.solve(gp.GPConfig(n=12, nt=20, tol=0.5, check_every=10))
    assert r["iters"] == 20 and not r["tripped"]
    np.testing.assert_allclose(r["drift"], r_fix["drift"], rtol=1e-3,
                               atol=1e-7)
    # tripwire tol: stops at the first check that exceeds it
    r2 = gp.solve(gp.GPConfig(n=12, nt=200, tol=1e-6, check_every=5))
    assert r2["tripped"] and r2["iters"] < 200


# ---------------------------------------------------------------- distributed
def test_distributed_partials_pmax_psum():
    out = run_subprocess("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from jax import shard_map
from repro.core import init_parallel_stencil, fd3d as fd
from repro.distributed import halo, overlap
from repro.launch.mesh import make_mesh

mesh = make_mesh((2, 2), ("x", "y"))
Ng, Nz = 34, 10
rng = np.random.RandomState(0)
Tg = jnp.asarray(rng.rand(Ng, Ng, Nz), jnp.float32)
Cig = jnp.asarray(rng.rand(Ng, Ng, Nz) + 0.5, jnp.float32)
sc = dict(lam=1.0, dt=1e-4, _dx=1.0, _dy=1.0, _dz=1.0)

ps = init_parallel_stencil(backend="jnp", ndims=3)
@ps.parallel(outputs=("T2",), rotations={"T2": "T"},
             reductions={"err": "max_abs_diff(T2, T)"})
def kern(T2, T, Ci, lam, dt, _dx, _dy, _dz):
    return {"T2": fd.inn(T) + dt*(lam*fd.inn(Ci)*(fd.d2_xi(T)*_dx**2
            + fd.d2_yi(T)*_dy**2 + fd.d2_zi(T)*_dz**2))}

# single-device reference: one step + whole-array check
Tr, reds_ref = kern(T2=Tg, T=Tg, Ci=Cig, **sc)
err_ref = float(reds_ref["err"])

lT = halo.global_to_local(Tg, (2, 2)); lC = halo.global_to_local(Cig, (2, 2))
ls = lT[0].shape
Ts = jnp.asarray(np.stack(lT).reshape(2, 2, *ls))
Cs = jnp.asarray(np.stack(lC).reshape(2, 2, *ls))

def one(Tl, Cl):
    Tl, Cl = Tl[0, 0], Cl[0, 0]
    (out, reds), fresh = overlap.sequential_step(
        kern, dict(T2=Tl, T=Tl, Ci=Cl), sc, ("T",), ("x", "y"))
    (out2, reds2), _ = overlap.overlapped_step(
        kern, dict(T2=Tl, T=Tl, Ci=Cl), sc, ("T",), ("x", "y"))
    return out[None, None], reds["err"][None], reds2["err"][None]

f = shard_map(one, mesh=mesh, in_specs=(P("x","y"), P("x","y")),
              out_specs=(P("x","y"), P("x"), P("x")), check_vma=False)
outs, errs, errs2 = f(Ts, Cs)
# the pmax'd error is replicated across ranks and equals the global check
errs = np.unique(np.asarray(errs)); errs2 = np.unique(np.asarray(errs2))
assert errs.size == 1 and errs2.size == 1, (errs, errs2)
print("PMAX_ERRS", float(errs[0]), float(errs2[0]), err_ref)
np.testing.assert_allclose(errs[0], err_ref, rtol=1e-5)
np.testing.assert_allclose(errs2[0], err_ref, rtol=1e-5)

# psum partials: each rank's fused sum_sq value is a valid partial —
# ONE psum combines them to the sum of the rank-local folds (equal to
# the global fold exactly when rank domains are disjoint; these local
# arrays carry ghost rings, so the reference below folds the same
# ghost-extended domains)
@ps.parallel(outputs=("T2",), rotations={"T2": "T"},
             reductions={"m": "sum_sq(T2)"})
def kern2(T2, T, Ci, lam, dt, _dx, _dy, _dz):
    return {"T2": fd.inn(T) + dt*(lam*fd.inn(Ci)*(fd.d2_xi(T)*_dx**2
            + fd.d2_yi(T)*_dy**2 + fd.d2_zi(T)*_dz**2))}

def rank_sum(Tl, Cl):
    Tl, Cl = Tl[0, 0], Cl[0, 0]
    out, reds = kern2(T2=Tl, T=Tl, Ci=Cl, **sc)
    total = overlap.finish_reductions(kern2, reds, ("x", "y"))
    return out[None, None], total["m"][None]

g = shard_map(rank_sum, mesh=mesh, in_specs=(P("x","y"), P("x","y")),
              out_specs=(P("x","y"), P("x")), check_vma=False)
outs2, masses = g(Ts, Cs)
# reference: the same per-shard kernel runs on host; psum == sum of the
# disjoint shard folds
want = sum(float(jnp.sum(kern2.with_reductions(None)(
    T2=jnp.asarray(t), T=jnp.asarray(t), Ci=jnp.asarray(c), **sc) ** 2))
    for t, c in zip(lT, lC))
masses = np.asarray(masses)
print("PSUM_MASS", float(masses[0]), want)
np.testing.assert_allclose(masses, want, rtol=1e-5)
print("DIST_REDS_OK")
""", n_devices=4)
    assert "DIST_REDS_OK" in out


@pytest.mark.parametrize("factors", [(2, 2), (2, 1), (1, 2)])
def test_distributed_checks_equal_the_global_check(factors):
    """From the second step on, an output's ghost ring holds the previous
    step: the rank-combined checks of sequential_step, overlapped_step and
    elastic_solve_until fold the owned cells only, so max and sum kinds
    equal the whole-array check of a one-device run at every step."""
    out = run_subprocess(f"""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from jax import shard_map
from repro.core import init_parallel_stencil, fd3d as fd
from repro.distributed import elastic, halo, overlap
from repro.launch.mesh import make_mesh

factors, nt = {factors}, 2
mesh = make_mesh(factors, ("x", "y"))
x, y, z = np.meshgrid(*[np.linspace(0, 1, n) for n in (18, 18, 6)],
                      indexing="ij")
Tg = jnp.asarray(1 + np.exp(-((x - .45)**2 + (y - .55)**2) / .02), jnp.float32)
Cig = jnp.full(Tg.shape, 0.5, jnp.float32)
sc = dict(lam=1.0, dt=1e-3, _dx=17.0, _dy=17.0, _dz=5.0)
ps = init_parallel_stencil(backend="jnp", ndims=3)
checks = {{"err": "max_abs_diff(T2, T)", "m": "sum_sq(T2)"}}

@ps.parallel(outputs=("T2",), rotations={{"T2": "T"}}, reductions=checks)
def kern(T2, T, Ci, lam, dt, _dx, _dy, _dz):
    return {{"T2": fd.inn(T) + dt*(lam*fd.inn(Ci)*(fd.d2_xi(T)*_dx**2
            + fd.d2_yi(T)*_dy**2 + fd.d2_zi(T)*_dz**2))}}

want, T2, T = [], Tg, Tg
for _ in range(nt):
    out, reds = kern(T2=T2, T=T, Ci=Cig, **sc)
    T2, T = T, out
    want.append([float(reds["err"]), float(reds["m"])])

def stack(g):
    loc = halo.global_to_local(g, factors)
    return jnp.asarray(np.stack(loc).reshape(*factors, *loc[0].shape))

def run(step):
    def body(Tl, Cl):
        T2, T, Ci = Tl[0, 0], Tl[0, 0], Cl[0, 0]
        got = []
        for _ in range(nt):
            (out, reds), fresh = step(kern, dict(T2=T2, T=T, Ci=Ci), sc,
                                      ("T",), ("x", "y"))
            T2, T = fresh["T"], out
            got.append(jnp.stack([reds["err"], reds["m"]]))
        return jnp.stack(got)[None]
    f = shard_map(body, mesh=mesh, in_specs=(P("x", "y"), P("x", "y")),
                  out_specs=P(("x", "y")), check_vma=False)
    return np.asarray(f(stack(Tg), stack(Cig)))

for step in (overlap.sequential_step, overlap.overlapped_step):
    for got in run(step):   # every rank sees the same combined values
        np.testing.assert_allclose(got, want, rtol=1e-5)
res = elastic.elastic_solve_until(
    kern, dict(T2=np.asarray(Tg), T=np.asarray(Tg), Ci=np.asarray(Cig)), sc,
    factors=factors, tol=0.0, max_iters=nt, exchange=("T",), check_every=1,
    error="err")
np.testing.assert_allclose(float(res.err), want[-1][0], rtol=1e-5)
np.testing.assert_allclose(np.asarray(res.output(kern)), np.asarray(T),
                           rtol=1e-6)
print("OWNED_CHECKS_OK")
""", n_devices=4)
    assert "OWNED_CHECKS_OK" in out
