"""Example solvers as importable modules (paper §3's translated solvers):
golden-value regression, conservation bounds, and jnp-vs-pallas backend
parity of the coupled stencil engine on small grids."""
import jax.numpy as jnp
import numpy as np
import pytest

from examples import gross_pitaevskii as gp
from examples import porosity_waves as pw

# jnp backend, n=32, nt=40 (default physics constants). Regenerate with:
#   PYTHONPATH=src:. python -c "from examples.porosity_waves import *; \
#       print(solve(PorosityConfig(n=32, nt=40)))"
POROSITY_GOLDEN = {
    "phi_min": 0.009992753155529499,
    "phi_max": 0.010957718826830387,
    "pe_absmax": 0.0024534445255994797,
    "phi_sum": 10.255167961120605,
}


def test_porosity_golden_regression():
    r = pw.solve(pw.PorosityConfig(n=32, nt=40))
    assert np.isclose(r["phi_min"], POROSITY_GOLDEN["phi_min"], rtol=1e-4)
    assert np.isclose(r["phi_max"], POROSITY_GOLDEN["phi_max"], rtol=1e-4)
    assert np.isclose(r["pe_absmax"], POROSITY_GOLDEN["pe_absmax"], rtol=5e-4)
    assert np.isclose(float(jnp.sum(r["phi"])), POROSITY_GOLDEN["phi_sum"],
                      rtol=1e-5)


def test_porosity_backend_parity():
    """Same coupled one-launch update on jnp and interpreted pallas."""
    outs = {
        b: pw.solve(pw.PorosityConfig(n=24, nt=8, backend=b))
        for b in ("jnp", "pallas")
    }
    np.testing.assert_allclose(np.asarray(outs["jnp"]["phi"]),
                               np.asarray(outs["pallas"]["phi"]), atol=2e-6)
    np.testing.assert_allclose(np.asarray(outs["jnp"]["Pe"]),
                               np.asarray(outs["pallas"]["Pe"]), atol=2e-6)


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_porosity_flux_split_matches_fused(backend):
    """Explicit staggered flux fields (mixed-shape two-launch scheme) must
    reproduce the fused in-kernel-flux scheme."""
    fused = pw.solve(pw.PorosityConfig(n=24, nt=8, backend=backend))
    split = pw.solve(pw.PorosityConfig(n=24, nt=8, backend=backend,
                                       flux_split=True))
    np.testing.assert_allclose(np.asarray(fused["phi"]),
                               np.asarray(split["phi"]), atol=1e-7)
    np.testing.assert_allclose(np.asarray(fused["Pe"]),
                               np.asarray(split["Pe"]), atol=1e-7)


def test_gp_mass_conservation():
    r = gp.solve(gp.GPConfig(n=16, nt=40))
    assert np.isfinite(r["mass"])
    assert r["drift"] < 0.05
    # the wavefunction stays localized (no boundary blow-up)
    assert float(jnp.abs(r["re"][0]).max()) < 0.05


def test_gp_two_launch_mass_conservation():
    r = gp.solve(gp.GPConfig(n=16, nt=40, fused=False))
    assert r["drift"] < 0.05


def test_gp_backend_parity():
    """Fused coupled radius-2 kernel: jnp vs interpreted pallas."""
    outs = {
        b: gp.solve(gp.GPConfig(n=12, nt=6, backend=b)) for b in ("jnp", "pallas")
    }
    np.testing.assert_allclose(np.asarray(outs["jnp"]["re"]),
                               np.asarray(outs["pallas"]["re"]), atol=1e-6)
    np.testing.assert_allclose(np.asarray(outs["jnp"]["im"]),
                               np.asarray(outs["pallas"]["im"]), atol=1e-6)
    assert abs(outs["jnp"]["drift"] - outs["pallas"]["drift"]) < 1e-5


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_gp_fused_kernel_run_steps_bitwise(backend):
    """The radius-2 coupled GP kernel under k-step temporal blocking: one
    fused launch == k sequential coupled calls, bit for bit."""
    cfg = gp.GPConfig(n=12, backend=backend)
    grid, re, im, V = gp.init_state(cfg)
    dt = gp.timestep(grid)
    kern = gp.make_step(grid, cfg).kernels[0]
    inv2 = tuple(1.0 / d ** 2 for d in grid.spacing)
    sc = dict(V=V, g=cfg.g, dt=dt, _dx2=inv2[0], _dy2=inv2[1], _dz2=inv2[2])
    a, b, ia, ib = re, re.copy(), im, im.copy()
    for _ in range(2):
        o = kern(re2=b, im2=ib, re=a, im=ia, **sc)
        a, b = o["re2"], a
        ia, ib = o["im2"], ia
    got = kern.run_steps(2, re2=re.copy(), im2=im.copy(), re=re, im=im, **sc)
    np.testing.assert_array_equal(np.asarray(got["re2"]), np.asarray(a))
    np.testing.assert_array_equal(np.asarray(got["im2"]), np.asarray(ia))


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
@pytest.mark.parametrize("bc", ["neumann", "dirichlet", "periodic"])
def test_porosity_fused_bc_matches_postpass(backend, bc):
    """The --bc path (engine-fused boundary conditions) must equal the
    raw @inn kernel followed by the core.boundary post-pass: bitwise on
    jnp (identical program); to 1 ulp on pallas, where the bc and no-bc
    variants are two separately compiled programs whose interior
    arithmetic may contract FMAs differently (the per-kind bitwise
    in-program equality is covered in test_ir.py)."""
    from repro.core import boundary

    cfg_bc = pw.PorosityConfig(n=24, nt=6, backend=backend, bc=bc)
    cfg_raw = pw.PorosityConfig(n=24, nt=6, backend=backend, bc="none")
    grid, phi, Pe = pw.init_state(cfg_bc)
    dtau = pw.timestep(cfg_bc, grid)
    step_bc = pw.make_step(grid, cfg_bc)
    step_raw = pw.make_step(grid, cfg_raw)
    post = {
        "neumann": boundary.neumann0,
        "dirichlet": lambda a, v: boundary.dirichlet(a, v),
        "periodic": boundary.periodic,
    }
    p1, e1 = phi, Pe
    p2, e2 = phi, Pe
    for _ in range(cfg_bc.nt):
        p1, e1 = step_bc(p1, e1, dtau)
        rp, re_ = step_raw(p2, e2, dtau)
        if bc == "dirichlet":
            p2, e2 = post[bc](rp, cfg_bc.phi0), post[bc](re_, 0.0)
        else:
            p2, e2 = post[bc](rp), post[bc](re_)
    if backend == "jnp":
        np.testing.assert_array_equal(np.asarray(p1), np.asarray(p2))
        np.testing.assert_array_equal(np.asarray(e1), np.asarray(e2))
    else:
        np.testing.assert_allclose(np.asarray(p1), np.asarray(p2),
                                   rtol=1e-5, atol=1e-12)
        np.testing.assert_allclose(np.asarray(e1), np.asarray(e2),
                                   rtol=1e-5, atol=1e-12)


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_porosity_flux_split_bc_parity(backend):
    """The fused-BC path composes with the staggered flux-split scheme."""
    fused = pw.solve(pw.PorosityConfig(n=24, nt=8, backend=backend,
                                       bc="dirichlet"))
    split = pw.solve(pw.PorosityConfig(n=24, nt=8, backend=backend,
                                       bc="dirichlet", flux_split=True))
    np.testing.assert_allclose(np.asarray(fused["phi"]),
                               np.asarray(split["phi"]), atol=1e-7)


@pytest.mark.parametrize("bc", ["neumann", "dirichlet", "periodic"])
def test_gp_fused_bc_matches_postpass(bc):
    """GP --bc routed through the fused coupled kernel == raw kernel +
    post-pass (jnp backend; pallas parity is covered per-kind in
    test_ir.py)."""
    from repro.core import boundary

    cfg_bc = gp.GPConfig(n=12, nt=4, bc=bc)
    cfg_raw = gp.GPConfig(n=12, nt=4, bc="none")
    grid, re, im, V = gp.init_state(cfg_bc)
    dt = gp.timestep(grid)
    step_bc = gp.make_step(grid, cfg_bc)
    step_raw = gp.make_step(grid, cfg_raw)
    post = {"neumann": boundary.neumann0,
            "dirichlet": lambda a: boundary.dirichlet(a, 0.0),
            "periodic": boundary.periodic}[bc]
    r1, i1 = re, im
    r2, i2 = re, im
    for _ in range(cfg_bc.nt):
        r1, i1 = step_bc(r1, i1, dt, V)
        rr, ri = step_raw(r2, i2, dt, V)
        r2, i2 = post(rr), post(ri)
    np.testing.assert_array_equal(np.asarray(r1), np.asarray(r2))
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))


def test_cli_main_smoke(capsys):
    pw.main(["--n", "32", "--nt", "3"])
    assert "porosity wave" in capsys.readouterr().out
    pw.main(["--n", "32", "--nt", "3", "--bc", "periodic"])
    assert "bc=periodic" in capsys.readouterr().out
    gp.main(["--n", "12", "--nt", "2"])
    assert "GP:" in capsys.readouterr().out
    gp.main(["--n", "12", "--nt", "2", "--bc", "dirichlet"])
    assert "GP:" in capsys.readouterr().out


def test_stokes3d_example_runs_to_tolerance(capsys):
    """The Stokes3D example as a user runs it: four launches per
    pseudo-transient iteration through ``solve_until``, to a tolerance."""
    from examples import stokes3d

    stokes3d.main(["--n", "15", "--tol", "1e-4", "--max-iters", "300"])
    out = capsys.readouterr().out
    assert out.startswith("Stokes3D: ") and "[jnp]" in out
    iters = int(out.split()[1])
    assert 0 < iters < 300 and iters % 10 == 0


def test_stokes3d_backend_parity():
    """The four staggered launches on jnp and on interpreted Pallas."""
    from examples import stokes3d

    res = {b: stokes3d.solve(stokes3d.StokesConfig(
        n=15, tol=0.0, max_iters=20, backend=b)) for b in ("jnp", "pallas")}
    for k in ("Vx", "Vy", "Vz", "Pt", "txy", "Rz"):
        a, b = (np.asarray(res[x].fields[k]) for x in ("jnp", "pallas"))
        np.testing.assert_allclose(b, a, rtol=0,
                                   atol=1e-5 * np.max(np.abs(a)), err_msg=k)
    assert abs(float(res["jnp"].err) - float(res["pallas"].err)) \
        <= 1e-4 * float(res["jnp"].err)
