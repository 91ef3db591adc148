"""Streaming (marching-axis) execution: `march_axis=` slides one grid
axis sequentially, reusing VMEM plane queues instead of refetching halo
windows. Streamed results must equal the all-parallel path — bitwise
within one compiled program, 1-ulp (`allclose(atol≈1e-6)`) across
separately compiled programs — for plain, coupled/staggered,
asymmetric-halo and temporally-blocked kernels on both backends, with a
graceful fallback when the march extent cannot fill the plane queue and
pointed errors for unsupported geometries."""
import numpy as np
import jax.numpy as jnp
import pytest

from conftest import run_subprocess
from repro import telemetry
from repro.core import fd2d, fd3d, init_parallel_stencil, teff
from repro.ir import BoundaryCondition
from repro.kernels import autotune
from repro.kernels.stencil import build_stencil_call
from repro.ir import cost as _roofline

SHAPE3 = (20, 16, 24)
SC3 = dict(lam=1.0, dt=1e-4, _dx=float(SHAPE3[0] - 1),
           _dy=float(SHAPE3[1] - 1), _dz=float(SHAPE3[2] - 1))


def _diffusion(backend, march=None, tile=None):
    ps = init_parallel_stencil(backend=backend, ndims=3)

    @ps.parallel(outputs=("T2",), rotations={"T2": "T"}, march_axis=march,
                 tile=tile)
    def kern(T2, T, Ci, lam, dt, _dx, _dy, _dz):
        return {"T2": fd3d.inn(T) + dt * (lam * fd3d.inn(Ci) * (
            fd3d.d2_xi(T) * _dx ** 2 + fd3d.d2_yi(T) * _dy ** 2 +
            fd3d.d2_zi(T) * _dz ** 2))}
    return kern


def _fields3(rng):
    T = jnp.asarray(rng.rand(*SHAPE3), jnp.float32)
    return T.copy(), T, jnp.asarray(rng.rand(*SHAPE3) + 0.5, jnp.float32)


# ---------------------------------------------------------------------------
# plain kernel: streamed == all-parallel on every axis, both backends
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("backend", ["jnp", "pallas"])
@pytest.mark.parametrize("march", [0, 1, 2])
def test_streamed_matches_parallel(backend, march, rng):
    T2, T, Ci = _fields3(rng)
    want = np.asarray(_diffusion("jnp")(T2=T2, T=T, Ci=Ci, **SC3))
    k = _diffusion(backend, march=march, tile=(4, 4, 8))
    got = np.asarray(k(T2=T2, T=T, Ci=Ci, **SC3))
    np.testing.assert_allclose(got, want, atol=1e-6)
    if backend == "pallas":
        run = next(iter(k._cache.values()))
        assert run.march_axis == march and not run.march_fallback
        assert run.queue_planes > 0


# ---------------------------------------------------------------------------
# the default derivation streams axis 0 where all-parallel blocks refetch it
# ---------------------------------------------------------------------------
@pytest.fixture
def collector():
    col = telemetry.configure(None)
    yield col
    telemetry.reset()


def _builds(col, path):
    return col.counters.get(
        ("stencil.builds", (("fn", "k"), ("path", path))), 0)


N = 512
GP = dict(n_fields=5, radius=2, halos=((2, 2),) * 3, n_out=2)


@pytest.mark.parametrize("case,kw,march,path", [
    ("fig1-512", dict(shape=(N,) * 3, bm=1), 0, "streamed"),
    ("rank-514", dict(shape=(N + 2,) * 3, bm=1), 0, "streamed"),
    # five radius-2 frames fit the VMEM budget only one plane deep
    ("gp-512", dict(shape=(N,) * 3, bm=1, **GP), 0, "streamed"),
    # a fused neumann0 shift needs two planes in the block
    ("neumann0-512", dict(shape=(N,) * 3, bm=2, bc={
        "f0": BoundaryCondition("neumann0")}), 0, "streamed"),
    ("grid-16", dict(shape=(16,) * 3), None, "whole"),
    ("2d-512", dict(shape=(N, N)), None, "whole"),
    ("staggered-axis0", dict(shape=(N,) * 3,
                             field_shapes={"f0": (N - 1, N, N)}),
     None, "refetched"),
    ("explicit-tile", dict(shape=(N,) * 3, tile=(2, N, N)), None,
     "refetched"),
    ("explicit-march", dict(shape=(N,) * 3, march_axis=1), 1, "streamed"),
])
def test_default_launch_streams_leading_axis(collector, case, kw, march,
                                             path):
    """Geometry alone (nothing is traced): a launch the caller left to
    the derivation streams along axis 0 when its all-parallel blocks
    would tile axis 0 with a halo; a one-block axis 0, a 2-D grid, a
    field staggered on axis 0 and an explicit tile keep the all-parallel
    launch, and an explicit march axis is built as given."""
    kw = dict(kw)
    bm = kw.pop("bm", None)
    names = tuple(f"f{i}" for i in range(kw.pop("n_fields", 3)))
    run = build_stencil_call(
        lambda f, s: {}, name="k", field_names=names,
        out_names=names[:kw.pop("n_out", 1)], scalar_names=(),
        radius=kw.pop("radius", 1), dtype="float32", interpret=True, **kw)
    assert run.march_axis == march and not run.march_fallback
    if "tile" in kw:
        assert run.block == kw["tile"]
    if bm is not None:
        # a march block of halo depth (or the least that fits): each
        # plane is fetched once, plus one drain block
        assert run.block[0] == bm and run.grid[0] == kw["shape"][0] // bm
    assert (run.grid[0] == 1) == (path == "whole")
    assert _builds(collector, path) == 1
    assert sum(v for k, v in collector.counters.items()
               if k[0] == "stencil.builds") == 1
    # the build also counts its outputs stored at an extent shorter than
    # the base: only the output staggered on axis 0 is
    assert collector.counters[("stencil.own_extent_outputs", (("fn", "k"),))] \
        == (case == "staggered-axis0")


def test_default_launch_streams_in_interpret_mode(collector, rng):
    """On a grid whose axis 0 the all-parallel derivation tiles, the
    default launch streams, equals the all-parallel launch pinned by an
    explicit tile, and counts one streamed build however often it runs."""
    shape = (64, 8, 128)
    T = jnp.asarray(rng.rand(*shape), jnp.float32)
    Ci = jnp.asarray(rng.rand(*shape) + 0.5, jnp.float32)
    k = _diffusion("pallas")
    got = np.asarray(k(T2=T, T=T, Ci=Ci, **SC3))
    k(T2=T, T=T, Ci=Ci, **SC3)
    (info,) = k.launch_info.values()
    assert info["march_axis"] == 0 and info["grid"] == (64, 1, 1)
    assert collector.counters[("stencil.builds",
                               (("fn", "kern"), ("path", "streamed")))] == 1
    pinned = _diffusion("pallas", tile=(16, 8, 128))
    want = np.asarray(pinned(T2=T, T=T, Ci=Ci, **SC3))
    (pinfo,) = pinned.launch_info.values()
    assert pinfo["march_axis"] is None and pinfo["grid"] == (4, 1, 1)
    np.testing.assert_allclose(got, want, atol=1e-6)


# ---------------------------------------------------------------------------
# temporal blocking: streamed k-step == all-parallel k-step, and the
# streamed kernel is self-consistent (fused vs sequential, same object)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("backend", ["jnp", "pallas"])
@pytest.mark.parametrize("k", [1, 2, 4])
def test_streamed_temporal_matches_parallel(backend, k, rng):
    T2, T, Ci = _fields3(rng)
    want = np.asarray(_diffusion(backend, tile=(4, 4, 8)).run_steps(
        k, T2=T2, T=T, Ci=Ci, **SC3))
    kern = _diffusion(backend, march=0, tile=(4, 4, 8))
    got = np.asarray(kern.run_steps(k, T2=T2, T=T, Ci=Ci, **SC3))
    np.testing.assert_allclose(got, want, atol=1e-6)
    if backend == "pallas" and k > 1:
        run = [v for kk, v in kern._cache.items() if kk[3] == k][0]
        assert run.march_axis == 0 and not run.march_fallback


@pytest.mark.parametrize("k", [2, 4])
def test_streamed_run_steps_matches_own_sequential(k, rng):
    """The fused streamed k-step launch equals k sequential rotated calls
    of the same kernel object to 1 ulp (the fused program's shrinking
    sweep margins compile to different FMA contractions than the
    single-step windows, so this is a cross-program comparison — the
    engine's bitwise guarantee only holds within one compiled program)."""
    T2, T, Ci = _fields3(rng)
    kern = _diffusion("pallas", march=0, tile=(4, 4, 8))
    a, b = T2, T
    for _ in range(k):
        a = kern(T2=a, T=b, Ci=Ci, **SC3)
        a, b = b, a
    got = np.asarray(kern.run_steps(k, T2=T2, T=T, Ci=Ci, **SC3))
    np.testing.assert_allclose(got, np.asarray(b), atol=1e-6)
    # determinism within one compiled program: re-running the fused
    # launch on the same inputs is bitwise
    again = np.asarray(kern.run_steps(k, T2=T2, T=T, Ci=Ci, **SC3))
    np.testing.assert_array_equal(got, again)


# ---------------------------------------------------------------------------
# coupled / staggered systems
# ---------------------------------------------------------------------------
def _coupled2d(backend, march=None, tile=None):
    """phi2/Pe2 coupled outputs + a face-centered flux INPUT staggered
    along axis 0 (so march_axis=1 is the streamable one)."""
    ps = init_parallel_stencil(backend=backend, ndims=2)

    @ps.parallel(outputs=("phi2", "Pe2"), march_axis=march, tile=tile,
                 rotations={"phi2": "phi", "Pe2": "Pe"})
    def kern(phi2, Pe2, phi, Pe, qx, dtau):
        div = qx[1:, 1:-1] - qx[:-1, 1:-1]
        return {
            "phi2": fd2d.inn(phi) + dtau * (fd2d.d2_xi(phi) + fd2d.d2_yi(phi)
                                            - div),
            "Pe2": fd2d.inn(Pe) + dtau * (fd2d.d2_xi(Pe) + fd2d.d2_yi(Pe)
                                          + fd2d.inn(phi)),
        }
    return kern


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
@pytest.mark.parametrize("k", [1, 2])
def test_streamed_coupled_staggered(backend, k, rng):
    n = 24
    phi = jnp.asarray(rng.rand(n, n), jnp.float32)
    Pe = jnp.asarray(rng.rand(n, n), jnp.float32)
    qx = jnp.asarray(rng.rand(n - 1, n), jnp.float32)
    args = dict(phi2=phi, Pe2=Pe, phi=phi, Pe=Pe, qx=qx, dtau=1e-3)
    want = _coupled2d("jnp").run_steps(k, **args)
    kern = _coupled2d(backend, march=1, tile=(4, 4))
    got = kern.run_steps(k, **args)
    for o in ("phi2", "Pe2"):
        np.testing.assert_allclose(np.asarray(got[o]), np.asarray(want[o]),
                                   atol=1e-6)


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_streamed_staggered_march_axis_raises(backend, rng):
    n = 24
    phi = jnp.asarray(rng.rand(n, n), jnp.float32)
    Pe = jnp.asarray(rng.rand(n, n), jnp.float32)
    qx = jnp.asarray(rng.rand(n - 1, n), jnp.float32)
    kern = _coupled2d(backend, march=0, tile=(4, 4))
    with pytest.raises(ValueError, match="staggered"):
        kern(phi2=phi, Pe2=Pe, phi=phi, Pe=Pe, qx=qx, dtau=1e-3)


def test_march_axis_out_of_range():
    ps = init_parallel_stencil(backend="jnp", ndims=2)
    with pytest.raises(ValueError, match="out of range"):
        ps.parallel(outputs=("T2",), march_axis=2)


# ---------------------------------------------------------------------------
# asymmetric (upwind) footprints
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("backend", ["jnp", "pallas"])
@pytest.mark.parametrize("march", [0, 1])
def test_streamed_upwind_asymmetric(backend, march, rng):
    def upwind(T2, T, dt):
        return {"T2": fd2d.inn(T) + dt * (T[:-2, 1:-1] - T[1:-1, 1:-1])}

    U = jnp.asarray(rng.rand(20, 24), jnp.float32)
    ps = init_parallel_stencil(backend="jnp", ndims=2)
    want = np.asarray(ps.parallel(outputs=("T2",))(upwind)(T2=U, T=U, dt=1e-3))
    ps = init_parallel_stencil(backend=backend, ndims=2)
    k = ps.parallel(outputs=("T2",), march_axis=march, tile=(4, 4))(upwind)
    got = np.asarray(k(T2=U, T=U, dt=1e-3))
    np.testing.assert_allclose(got, want, atol=1e-6)


# ---------------------------------------------------------------------------
# fallback: march extent smaller than the plane queue
# ---------------------------------------------------------------------------
def test_streamed_fallback_small_march_extent(rng):
    T2, T, Ci = _fields3(rng)
    # k=4 sweeps need a 3-block (30-plane) queue at tile 10 > 20 planes
    kern = _diffusion("pallas", march=0, tile=(10, 4, 8))
    want = np.asarray(_diffusion("pallas", tile=(10, 4, 8)).run_steps(
        4, T2=T2, T=T, Ci=Ci, **SC3))
    got = np.asarray(kern.run_steps(4, T2=T2, T=T, Ci=Ci, **SC3))
    np.testing.assert_array_equal(got, want)
    run = [v for kk, v in kern._cache.items() if kk[3] == 4][0]
    assert run.march_axis is None and run.march_fallback


def test_jnp_march_fallback_tiny_axis(rng):
    """A march extent smaller than one slab degenerates to the plain jnp
    realization (identical semantics, no crash)."""
    U = jnp.asarray(rng.rand(3, 24), jnp.float32)
    ps = init_parallel_stencil(backend="jnp", ndims=2)

    def lap(T2, T, dt):
        return {"T2": fd2d.inn(T) + dt * (fd2d.d2_xi(T) + fd2d.d2_yi(T))}

    want = np.asarray(ps.parallel(outputs=("T2",))(lap)(T2=U, T=U, dt=1e-3))
    got = np.asarray(ps.parallel(outputs=("T2",), march_axis=0)(lap)(
        T2=U, T=U, dt=1e-3))
    np.testing.assert_allclose(got, want, atol=1e-6)


# ---------------------------------------------------------------------------
# marched() variants and the overlapped interior
# ---------------------------------------------------------------------------
def test_marched_variant_memoized(rng):
    kern = _diffusion("pallas", tile=(4, 4, 8))
    assert kern.marched(None) is kern
    m0 = kern.marched(0)
    assert m0 is kern.marched(0)
    assert m0.march_axis == 0 and kern.march_axis is None


def test_overlapped_step_streamed_interior():
    """@hide_communication with a streamed bulk update: the overlapped
    result equals the sequential exchange-then-update reference (shell
    slabs stay all-parallel; only the interior launch marches)."""
    out = run_subprocess("""
import jax, jax.numpy as jnp, numpy as np
from jax import shard_map
from jax.sharding import PartitionSpec as P
from repro.core import init_parallel_stencil, fd2d
from repro.distributed import halo, overlap
from repro.launch.mesh import make_mesh

mesh = make_mesh((4,), ("x",))
ps = init_parallel_stencil(backend="jnp", ndims=2)

@ps.parallel(outputs=("U2",))
def kern(U2, U, dt):
    return {"U2": fd2d.inn(U) + dt * (fd2d.d2_xi(U) + fd2d.d2_yi(U))}

rng = np.random.RandomState(0)
Ng = 4 * 16 + 2
Ug = jnp.asarray(rng.rand(Ng, 20), jnp.float32)

locs = halo.global_to_local(Ug, (4,), radius=1)
Us = jnp.asarray(np.stack(locs))
sc = dict(dt=1e-3)

def step(Ul):
    Ul = Ul[0]
    fields = dict(U2=Ul, U=Ul)
    seq, _ = overlap.sequential_step(kern, fields, sc, ("U",), ("x",))
    ovl, _ = overlap.overlapped_step(kern, fields, sc, ("U",), ("x",),
                                     march_axis=0)
    return seq[None], ovl[None]

f = shard_map(step, mesh=mesh, in_specs=P("x"), out_specs=(P("x"), P("x")),
              check_vma=False)
seq, ovl = f(Us)
d = float(np.max(np.abs(np.asarray(seq) - np.asarray(ovl))))
assert d < 1e-6, d
print("MARCH_OVERLAP_OK", d)
""", n_devices=4)
    assert "MARCH_OVERLAP_OK" in out


# ---------------------------------------------------------------------------
# analytic streamed-bytes model + autotune integration
# ---------------------------------------------------------------------------
def test_streamed_bytes_model_drops_march_overlap(rng):
    kern = _diffusion("jnp")
    cost = kern.cost_model(T2=SHAPE3, T=SHAPE3, Ci=SHAPE3, **SC3)
    tile = (4, 4, 8)
    refetched = cost.fetched_bytes_per_step(tile, 2)
    streamed = cost.a_eff_streamed(tile, 2, march_axis=0)
    assert streamed < refetched
    # the streamed model still exceeds the ideal once-per-sweep traffic
    assert streamed > cost.a_eff_bytes(2)
    # the teff-level factors tell the same story
    full = teff.window_overlap_factor(tile, cost.halo, 2)
    rest = teff.window_overlap_factor(tile, cost.halo, 2, march_axis=0)
    assert rest < full
    n = int(np.prod(SHAPE3))
    assert teff.a_eff_streamed(n, 2, 1, 4, nsteps=2, overlap=rest) < \
        teff.a_eff_streamed(n, 2, 1, 4, nsteps=2, overlap=full)


def test_roofline_records_streamed_traffic(rng):
    kern = _diffusion("jnp")
    cost = kern.cost_model(T2=SHAPE3, T=SHAPE3, Ci=SHAPE3, **SC3)
    rec = _roofline.stencil_roofline(cost, nsteps=2, tile=(4, 4, 8),
                                     march_axis=0)
    assert rec["streamed_bytes_per_step"] < rec["refetched_bytes_per_step"]
    assert rec["march_axis"] == 0


def test_autotune_march_candidates_and_cache_version(tmp_path, rng):
    path = str(tmp_path / "tune.json")
    # an old-format (pre-versioned) cache file must be ignored, not
    # crashed on — and gets rewritten in the new format
    import json
    with open(path, "w") as f:
        json.dump({"[\"old\"]": {"tile": [8, 8, 8], "nsteps": 1,
                                 "per_step_s": 1e-9}}, f)
    assert autotune._load_cache(path) == {}
    autotune._CACHE.clear()
    r = autotune.autotune_diffusion3d(
        (16, 16, 16), nsteps_candidates=(1, 2), iters=1, cache_path=path,
        march_candidates=(None, 0))
    assert r.march_axis in (None, 0)
    assert r.candidates_tried >= 1
    with open(path) as f:
        disk = json.load(f)
    assert disk["version"] == autotune.CACHE_VERSION
    # the winner round-trips through the versioned cache
    autotune._CACHE.clear()
    r2 = autotune.autotune_diffusion3d(
        (16, 16, 16), nsteps_candidates=(1, 2), iters=1, cache_path=path,
        march_candidates=(None, 0))
    assert r2 == r


def test_autotune_march_prunes_with_cost_model(rng):
    autotune._CACHE.clear()
    r = autotune.autotune_diffusion3d(
        (16, 16, 16), nsteps_candidates=(1, 2), iters=1,
        hw=teff.TPU_V5E, prune_ratio=1.05,
        march_candidates=(None, 0))
    # the analytic model ranks (tile, k, march) candidates; with a tight
    # ratio at least one config must have been dropped pre-compile
    assert r.candidates_pruned >= 1


def test_autotune_march_distinct_cache_keys():
    k1 = autotune.cache_key((8, 8), "float32", 1, 3, "t", (1,))
    k2 = autotune.cache_key((8, 8), "float32", 1, 3, "t", (1,),
                            march_candidates=(None, 0))
    k3 = autotune.cache_key((8, 8), "float32", 1, 3, "t", (1,),
                            halos=((1, 0), (0, 0)))
    assert len({k1, k2, k3}) == 3
