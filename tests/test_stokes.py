"""The Stokes3D accelerated pseudo-transient solver (``examples/stokes3d.py``):
four dependent ``@parallel`` launches per iteration through
``solve_until``, against the benchmark's plain whole-array reference
(``bench/references/stokes3d.py``), on the jnp backend at 31^3 cells and
on interpreted Pallas at 15^3."""
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from examples import stokes3d
from repro import telemetry
from repro.core import iterate

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEED = 2 ** 33 + 5


def _reference():
    path = os.path.join(ROOT, "bench", "references", "stokes3d.py")
    spec = importlib.util.spec_from_file_location("bench_reference_stokes3d",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _config(n):
    """The benchmark's configuration at ``n`` cells per axis."""
    with open(os.path.join(ROOT, "bench", "configs",
                           "stokes3d-383.json")) as f:
        cfg = json.load(f)
    return dict(cfg, grid=[n] * 3)


def _example_config(cfg, n, backend):
    return stokes3d.StokesConfig(
        n=n, length=cfg["length"][0], mu0=cfg["mu0"], mui=cfg["mui"],
        rho_gi=cfg["rho_gi"], radius=cfg["inclusion"]["radius"],
        re=cfg["re"], r=cfg["r"], cfl=cfg["cfl"], smooth=cfg["smooth_steps"],
        backend=backend)


def _own(ref, full, n):
    ext = ref.extents(n)
    return {k: full[k][tuple(slice(0, e) for e in ext[k])] for k in full}


def test_shapes_and_error_sizes():
    n = 9
    ref = _reference()
    assert stokes3d.shapes(n) == ref.extents(n)
    err = stokes3d.residual_error(n)
    reds = {"ss_Rx": 4.0, "ss_Ry": 0.0, "ss_Rz": 0.0, "ss_divV": 0.0}
    assert float(err(reds)) == pytest.approx(2.0 / ((n - 1) * (n - 2) ** 2))


def test_setup_matches_reference():
    """The example's set-up (the inclusion, ``maxloc`` through a
    ``@parallel`` launch with copied boundary, the pseudo-time steps)
    builds the reference's initial state."""
    n = 15
    ref, cfg = _reference(), _config(n)
    p = ref.params(cfg, SEED)
    full = _own(ref, ref.initial(cfg, p, tuple(cfg["grid"])), n)
    centre = tuple(c - l / 2 for c, l in zip(p["centre"], cfg["length"]))
    scfg = _example_config(cfg, n, "jnp")
    state = stokes3d.init_state(
        stokes3d.StokesConfig(**{**scfg.__dict__, "centre": centre}))
    assert set(state) == set(ref.STATE)
    assert float(jnp.min(state["Mus"])) < cfg["mu0"]     # the inclusion is in
    for k in ref.STATE:
        np.testing.assert_allclose(np.asarray(state[k]), np.asarray(full[k]),
                                   rtol=1e-6, err_msg=k)


@pytest.mark.parametrize("backend,n", [("jnp", 31), ("pallas", 15)])
def test_pt_solve_matches_reference(backend, n):
    """The engine's solve through ``solve_until`` (four launches per
    iteration) against the plain reference from the same seeded state:
    the err at every check of 30 iterations, the fields after 20, and the
    iteration a tolerance stops at, exactly."""
    ref, cfg = _reference(), _config(n)
    p = ref.params(cfg, SEED)
    full = ref.initial(cfg, p, tuple(cfg["grid"]))
    state0 = _own(ref, full, n)
    scfg = _example_config(cfg, n, backend)
    kernels, err = stokes3d.make_kernels(scfg), stokes3d.residual_error(n)
    sc = stokes3d.scalars(scfg)

    step, check = jax.jit(ref.step), jax.jit(ref.check_value)
    want, rs = [], full
    for c in range(3):
        for _ in range(10):
            rs = step(rs, p)
        want.append(float(check(rs)))
        if c == 1:
            at20 = _own(ref, rs, n)

    got, cur = [], state0
    for c in range(3):
        res = iterate.solve_until(kernels, cur, sc, tol=0.0, max_iters=10,
                                  check_every=10, error=err)
        assert int(res.iters) == 10
        cur = res.fields
        got.append(float(res.err))
        if c == 1:
            for k in ref.OUTPUTS + ref.RESIDUALS:
                w = np.asarray(at20[k])
                gap = np.max(np.abs(np.asarray(cur[k]) - w)) / np.max(np.abs(w))
                assert gap < 1e-5, (k, gap)
    np.testing.assert_allclose(got, want, rtol=1e-4)

    lo = sorted(want)
    tol = 0.5 * (lo[0] + lo[1])
    stop = 10 * (1 + next(i for i, e in enumerate(want) if e <= tol))
    res = iterate.solve_until(kernels, state0, sc, tol=tol, max_iters=1000,
                              check_every=10, error=err)
    assert int(res.iters) == stop
    assert float(res.err) <= tol
    for k in ref.MATERIAL:          # no launch writes them
        np.testing.assert_array_equal(np.asarray(res.fields[k]),
                                      np.asarray(state0[k]), err_msg=k)


def test_pt_solve_is_one_while_of_four_launches_per_iteration():
    """The whole solve is one ``while`` whose body holds ``check_every``
    passes of the four launches, in order; the checked pass runs the
    launches that declare reductions in their checked variant."""
    n = 9
    scfg = stokes3d.StokesConfig(n=n, backend="pallas")
    kernels = stokes3d.make_kernels(scfg)
    state = {k: jnp.zeros(s, jnp.float32)
             for k, s in stokes3d.shapes(n).items()}
    solver = iterate.make_solver(kernels, stokes3d.scalars(scfg),
                                 check_every=3,
                                 error=stokes3d.residual_error(n))
    jaxpr = jax.make_jaxpr(solver)(state, 1e-6, 100)
    (loop,) = [e for e in jaxpr.eqns if e.primitive.name == "while"]
    calls = [e.params["name"] for e in loop.params["body_jaxpr"].jaxpr.eqns
             if e.primitive.name == "pallas_call"]
    assert calls == ["compute_P", "compute_tau", "compute_dV",
                     "compute_V"] * 3


def test_solve_launches_and_crops_are_counted():
    """``solve.launches{fn}`` counts the launches of one iteration once per
    solver build; ``stencil.own_extent_outputs{fn}`` each launch's outputs
    stored at an extent shorter than its base, once per build."""
    n = 7
    col = telemetry.configure(None)
    try:
        scfg = stokes3d.StokesConfig(n=n, backend="pallas")
        kernels = stokes3d.make_kernels(scfg)
        state = {k: jnp.zeros(s, jnp.float32)
                 for k, s in stokes3d.shapes(n).items()}
        solver = iterate.make_solver(kernels, stokes3d.scalars(scfg),
                                     check_every=2,
                                     error=stokes3d.residual_error(n))
        jax.make_jaxpr(solver)(state, 1e-6, 10)
        c = col.counters
        label = "compute_P+compute_tau+compute_dV+compute_V"
        assert c[("solve.launches", (("fn", label),))] == 4
        own = {k[1][0][1]: v for k, v in c.items()
                 if k[0] == "stencil.own_extent_outputs"}
        # compute_P and compute_dV build a plain and a checked variant;
        # compute_dV's base is the cell extent, so Rx.. and dVx.. are
        # shorter than it, its cell-extent fields not
        assert own == {"compute_P": 4, "compute_tau": 6, "compute_dV": 12,
                         "compute_V": 3}
        builds = {k[1][0][1]: v for k, v in c.items()
                  if k[0] == "stencil.builds"}
        assert builds == {"compute_P": 2, "compute_tau": 1, "compute_dV": 2,
                          "compute_V": 1}
    finally:
        telemetry.reset()
