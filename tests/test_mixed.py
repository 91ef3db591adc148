"""Mixed-precision storage (PR 7): fields stored bf16/f16, all stencil
arithmetic in f32. Parity of every execution shape (plain / coupled +
staggered / nsteps=k / march / solve_until) against the f32 reference
within the analytic storage-rounding bound, f32 accumulation of the
fused reduction epilogues, the dtype-aware autotune cache key, and the
int8 compressed-collective properties (round-trip error <= scale/2 per
block, int-sized psum payload on the wire)."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import run_subprocess
from repro.core import fd2d, fd3d, init_parallel_stencil, iterate
from repro.distributed import compression
from repro.kernels import autotune

SHAPE = (16, 12, 20)
SC = dict(lam=1.0, dt=1e-3, _dx=1.0, _dy=1.0, _dz=1.0)
LOW = ("bfloat16", "float16")


def _eps(dtype):
    return float(jnp.finfo(jnp.dtype(dtype)).eps)


def _diffusion(backend, dtype="float32", reductions=None, march=None,
               tile=None):
    ps = init_parallel_stencil(backend=backend, dtype=dtype, ndims=3)

    @ps.parallel(outputs=("T2",), rotations={"T2": "T"}, tile=tile,
                 march_axis=march, reductions=reductions)
    def kern(T2, T, Ci, lam, dt, _dx, _dy, _dz):
        return {"T2": fd3d.inn(T) + dt * (lam * fd3d.inn(Ci) * (
            fd3d.d2_xi(T) * _dx ** 2 + fd3d.d2_yi(T) * _dy ** 2 +
            fd3d.d2_zi(T) * _dz ** 2))}

    return kern


def _fields(rng, shape=SHAPE):
    T = jnp.asarray(rng.rand(*shape), jnp.float32)
    Ci = jnp.asarray(rng.rand(*shape) + 0.5, jnp.float32)
    return T, Ci


# -- parity: low storage vs f32 reference -------------------------------


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
@pytest.mark.parametrize("dtype", LOW)
def test_parity_plain(backend, dtype, rng):
    T, Ci = _fields(rng)
    want = np.asarray(_diffusion("jnp")(T2=T, T=T, Ci=Ci, **SC))
    k = _diffusion(backend, dtype)
    got = k(T2=T.astype(dtype), T=T.astype(dtype), Ci=Ci.astype(dtype), **SC)
    assert got.dtype == jnp.dtype(dtype)
    # inputs are rounded to storage once, the output once: a handful of
    # ulps around the f32 trajectory
    atol = 4 * _eps(dtype) * float(jnp.max(jnp.abs(T)))
    np.testing.assert_allclose(np.asarray(got, np.float32), want, atol=atol)
    # the untouched boundary is a pure storage copy — exact
    np.testing.assert_array_equal(np.asarray(got[0], np.float32),
                                  np.asarray(T.astype(dtype)[0], np.float32))


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_parity_coupled_staggered(backend, rng):
    n = 24
    phi = jnp.asarray(rng.rand(n, n), jnp.float32)
    Pe = jnp.asarray(rng.rand(n, n), jnp.float32)
    qx = jnp.asarray(rng.rand(n - 1, n), jnp.float32)

    def make(backend, dtype):
        ps = init_parallel_stencil(backend=backend, dtype=dtype, ndims=2)

        @ps.parallel(outputs=("phi2", "Pe2"),
                     rotations={"phi2": "phi", "Pe2": "Pe"})
        def kern(phi2, Pe2, phi, Pe, qx, dtau):
            div = qx[1:, 1:-1] - qx[:-1, 1:-1]
            return {"phi2": fd2d.inn(phi) + dtau * (
                        fd2d.d2_xi(phi) + fd2d.d2_yi(phi) - div),
                    "Pe2": fd2d.inn(Pe) + dtau * (
                        fd2d.d2_xi(Pe) + fd2d.d2_yi(Pe) + fd2d.inn(phi))}
        return kern

    args = dict(phi2=phi, Pe2=Pe, phi=phi, Pe=Pe, qx=qx, dtau=1e-3)
    want = make("jnp", "float32")(**args)
    lo = {k: (v.astype(jnp.bfloat16) if hasattr(v, "astype") else v)
          for k, v in args.items()}
    got = make(backend, "bfloat16")(**lo)
    atol = 4 * _eps("bfloat16")
    for o in ("phi2", "Pe2"):
        assert got[o].dtype == jnp.bfloat16
        np.testing.assert_allclose(np.asarray(got[o], np.float32),
                                   np.asarray(want[o]), atol=atol)


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
@pytest.mark.parametrize("k", [2, 4])
def test_parity_nsteps(backend, k, rng):
    T, Ci = _fields(rng)
    want = np.asarray(_diffusion("jnp").run_steps(k, T2=T, T=T, Ci=Ci, **SC))
    kern = _diffusion(backend, "bfloat16")
    got = kern.run_steps(k, T2=T.astype(jnp.bfloat16),
                         T=T.astype(jnp.bfloat16),
                         Ci=Ci.astype(jnp.bfloat16), **SC)
    # storage rounding re-enters the stencil every step: linear-in-k bound
    atol = 4 * k * _eps("bfloat16") * float(jnp.max(jnp.abs(T)))
    np.testing.assert_allclose(np.asarray(got, np.float32), want, atol=atol)


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_parity_march(backend, rng):
    T, Ci = _fields(rng, (16, 12, 16))
    lo = dict(T2=T.astype(jnp.bfloat16), T=T.astype(jnp.bfloat16),
              Ci=Ci.astype(jnp.bfloat16))
    plain = _diffusion(backend, "bfloat16", tile=(4, 4, 8))(**lo, **SC)
    marched = _diffusion(backend, "bfloat16", march=0,
                         tile=(4, 4, 8))(**lo, **SC)
    # same math in two launch geometries: at most one bf16 ulp apart
    np.testing.assert_allclose(np.asarray(marched, np.float32),
                               np.asarray(plain, np.float32),
                               atol=_eps("bfloat16"))


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_parity_solve_until(backend, rng):
    T, Ci = _fields(rng)
    reds = {"err": "max_abs_diff(T2, T)"}
    # a bf16-storage solve cannot resolve below one storage ulp of the
    # field (~2^-9 here): the tolerance must sit above it
    tol = 1e-2
    assert tol > _eps("bfloat16") * float(jnp.max(jnp.abs(T)))
    ref = iterate.solve_until(
        _diffusion("jnp", reductions=reds), dict(T2=T, T=T, Ci=Ci), SC,
        tol=tol, max_iters=200, check_every=4)
    kern = _diffusion(backend, "bfloat16", reductions=reds)
    res = iterate.solve_until(
        kern, dict(T2=T.astype(jnp.bfloat16), T=T.astype(jnp.bfloat16),
                   Ci=Ci.astype(jnp.bfloat16)), SC,
        tol=tol, max_iters=200, check_every=4)
    # the device-resident carry keeps the storage dtype end to end
    assert res.fields["T2"].dtype == jnp.bfloat16
    assert res.err <= tol and res.iters <= ref.iters + 8
    atol = 8 * _eps("bfloat16") * float(jnp.max(jnp.abs(T)))
    np.testing.assert_allclose(np.asarray(res.fields["T2"], np.float32),
                               np.asarray(ref.fields["T2"]), atol=atol)


# -- reductions accumulate at f32 under low-precision storage -----------


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_reductions_accumulate_f32(backend, rng):
    # 32^3 = 32768 summands: naive bf16 accumulation stalls once the
    # partial sum reaches ~256 (1 ulp = 2), losing the convergence
    # signal entirely; f32 accumulation tracks the f64 host reference.
    shape = (32, 32, 32)
    T = jnp.asarray(rng.rand(*shape), jnp.float32)
    Ci = jnp.asarray(rng.rand(*shape) + 0.5, jnp.float32)
    reds = {"s": "sum(T2)", "m2": "sum_sq(T2)", "mx": "max_abs(T2)"}
    kern = _diffusion(backend, "bfloat16", reductions=reds)
    out, got = kern(T2=T.astype(jnp.bfloat16), T=T.astype(jnp.bfloat16),
                    Ci=Ci.astype(jnp.bfloat16), **SC)
    host = np.asarray(out, np.float64)
    want = {"s": host.sum(), "m2": (host * host).sum(),
            "mx": np.abs(host).max()}
    for name, w in want.items():
        g = float(got[name])
        assert np.dtype(np.asarray(got[name]).dtype).itemsize >= 4, name
        rel = abs(g - w) / max(abs(w), 1e-30)
        # f32 accumulation: ~1e-5 relative; bf16 accumulation would be
        # off by >50% for the sums
        assert rel < 1e-3, (name, g, w, rel)


# -- autotune cache: dtype-aware key, old formats ignored ---------------


def test_autotune_cache_key_carries_dtypes():
    base = dict(shape=(32, 32), radius=1, n_fields=3, tag="t")
    k32 = autotune.cache_key(dtype="float32", dtypes=("float32", "float32"),
                             **base)
    kbf = autotune.cache_key(dtype="bfloat16", dtypes=("bfloat16", "float32"),
                             **base)
    assert k32 != kbf


def test_autotune_old_cache_format_ignored(tmp_path, rng):
    cache = str(tmp_path / "tune.json")
    stale = {"version": 3, "entries": {"whatever": {
        "tile": [1, 1], "nsteps": 1, "per_step_s": 0.0,
        "candidates_tried": 1}}}
    with open(cache, "w") as f:
        json.dump(stale, f)
    assert autotune._load_cache(cache) == {}

    shape = (16, 16)
    U = jnp.asarray(rng.rand(*shape), jnp.float32)

    def make_step(tile, k):
        ps = init_parallel_stencil(backend="jnp", ndims=2)
        kern = ps.parallel(outputs=("U2",), rotations={"U2": "U"})(
            lambda U2, U, dt: {"U2": fd2d.inn(U) + dt * (
                fd2d.d2_xi(U) + fd2d.d2_yi(U))})
        return lambda: kern.run_steps(k, U2=U, U=U, dt=1e-3)

    r = autotune.autotune(make_step, shape=shape, dtype="float32", radius=1,
                          n_fields=2, nsteps_candidates=(1,), iters=1,
                          tag="unit", cache_path=cache)
    assert r.nsteps == 1
    with open(cache) as f:
        disk = json.load(f)
    assert disk["version"] == autotune.CACHE_VERSION
    # the rewritten cache replaces (not merges) the stale-schema entries
    assert "whatever" not in disk["entries"]


def test_autotune_separate_entries_per_dtype(tmp_path, rng):
    cache = str(tmp_path / "tune.json")
    shape = (16, 16)

    def run(dtype):
        U = jnp.asarray(rng.rand(*shape), jnp.float32).astype(dtype)

        def make_step(tile, k):
            ps = init_parallel_stencil(backend="jnp", dtype=dtype, ndims=2)
            kern = ps.parallel(outputs=("U2",), rotations={"U2": "U"})(
                lambda U2, U, dt: {"U2": fd2d.inn(U) + dt * (
                    fd2d.d2_xi(U) + fd2d.d2_yi(U))})
            return lambda: kern.run_steps(k, U2=U, U=U, dt=1e-3)

        return autotune.autotune(make_step, shape=shape, dtype=dtype,
                                 radius=1, n_fields=2, nsteps_candidates=(1,),
                                 iters=1, tag="unit-dtype-pair",
                                 cache_path=cache)

    run("float32")
    run("bfloat16")
    with open(cache) as f:
        disk = json.load(f)
    assert len(disk["entries"]) == 2  # one per (storage, compute) pair


# -- int8 compressed collectives ----------------------------------------


def test_int8_roundtrip_error_bound_hypothesis():
    hypothesis = pytest.importorskip("hypothesis")
    from hypothesis import given, settings
    from hypothesis.extra import numpy as hnp

    @settings(max_examples=50, deadline=None)
    @given(hnp.arrays(np.float32, hnp.array_shapes(min_dims=1, max_dims=2,
                                                   min_side=1, max_side=300),
                      elements={"allow_nan": False, "allow_infinity": False,
                                # float32-exact bounds (2**100 ~ 1.27e30)
                                "min_value": -2.0 ** 100,
                                "max_value": 2.0 ** 100}))
    def check(x):
        g = jnp.asarray(x)
        q, scale, meta = compression.quantize_int8(g)
        assert q.dtype == jnp.int8
        dq = compression.dequantize_int8(q, scale, meta)
        # per-block bound: |x - dq| <= scale/2 everywhere in the block
        err = jnp.abs(dq - g)
        nb = scale.shape[0]
        flat = jnp.reshape(err, (-1,))
        pad = nb * compression.BLOCK - flat.shape[0]
        blocked = jnp.reshape(jnp.pad(flat, (0, pad)), (nb, -1))
        bound = jnp.maximum(scale[:, 0], 0.0) / 2 * (1 + 1e-6) + 1e-30
        assert bool(jnp.all(blocked <= bound[:, None]))

    check()


def test_compressed_psum_wire_payload_is_int_sized():
    # jaxpr inspection: the only array-valued psum must carry the int32-
    # accumulated int8 codes — never a dequantized float payload. (Scales
    # travel via pmax/psum of one scalar per block, a 1/BLOCK-sized side
    # channel.)
    g = jnp.zeros((4096,), jnp.float32)
    jaxpr = jax.make_jaxpr(
        lambda x: compression.compressed_psum(x, "i"),
        axis_env=[("i", 4)])(g)
    psums = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "psum"]
    assert psums, "compressed_psum lost its psum"
    payload_bytes = 0
    for e in psums:
        for v in e.invars:
            assert not jnp.issubdtype(v.aval.dtype, jnp.floating), (
                f"float payload {v.aval} crossed the wire")
            payload_bytes += v.aval.dtype.itemsize * int(
                np.prod(v.aval.shape))
    # int32 accumulation of int8 codes: 4 B/elt on the wire upper-bounds
    # the transport; the quantized representation itself is 1 B/elt + the
    # per-block scale side channel
    assert payload_bytes <= 4 * g.size + 8 * (g.size // compression.BLOCK + 1)


def test_compressed_psum_exactness_shared_scale():
    # shared per-block scales make dequantize(psum(int32)) EQUAL to
    # psum(dequantize): s * sum(q_r) == sum(s * q_r) exactly in f32,
    # because every rank multiplies by the same power-free shared scale
    out = run_subprocess("""
import jax, jax.numpy as jnp, numpy as np
from jax import shard_map
from jax.sharding import PartitionSpec as P
from repro.distributed import compression
from repro.launch.mesh import make_mesh
mesh = make_mesh((4,), ("pod",))
rng = np.random.RandomState(7)
g = jnp.asarray(rng.randn(4, 2048), jnp.float32)
def f(gl):
    red, _ = compression.compressed_psum(gl[0], "pod")
    return red[None]
red = shard_map(f, mesh=mesh, in_specs=(P("pod"),), out_specs=P("pod"),
                check_vma=False)(g)[0]
# host replay of the wire protocol: every rank quantizes against the
# SHARED per-block scale, codes sum in int32, one dequantize at the end
blocked = [compression._blockify(g[r])[0] for r in range(4)]
meta = compression._blockify(g[0])[1]
shared = jnp.max(jnp.stack(
    [jnp.max(jnp.abs(b), axis=1, keepdims=True) / 127.0
     for b in blocked]), 0)
shared = jnp.where(shared > 0, shared, 1.0)
codes = sum(jnp.clip(jnp.round(b / shared), -127, 127).astype(jnp.int32)
            for b in blocked)
want = compression.dequantize_int8(codes, shared, meta)
np.testing.assert_array_equal(np.asarray(red), np.asarray(want))
print("SHARED_SCALE_EXACT")
""", n_devices=4)
    assert "SHARED_SCALE_EXACT" in out
