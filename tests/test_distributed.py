"""Multi-device tests (subprocess with fake CPU devices): halo exchange,
comm/compute overlap (paper C6), compression, elastic."""
import numpy as np
import pytest

from conftest import run_subprocess


def test_halo_overlap_and_multistep():
    out = run_subprocess("""
import jax, jax.numpy as jnp, numpy as np
from jax import shard_map
from jax.sharding import PartitionSpec as P
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
@ps.parallel(outputs=("T2",))
def kern(T2, T, Ci, lam, dt, _dx, _dy, _dz):
    return {"T2": fd.inn(T) + dt*(lam*fd.inn(Ci)*(fd.d2_xi(T)*_dx**2
            + fd.d2_yi(T)*_dy**2 + fd.d2_zi(T)*_dz**2))}

# single-device reference: 3 steps
Tr = Tg
for _ in range(3):
    Tr = kern(T2=Tr, T=Tr, Ci=Cig, **sc)

lT = halo.global_to_local(Tg, (2, 2)); lC = halo.global_to_local(Cig, (2, 2))
ls = lT[0].shape
Ts = jnp.asarray(np.stack(lT).reshape(2, 2, *ls))
Cs = jnp.asarray(np.stack(lC).reshape(2, 2, *ls))

def steps(Tl, Cl):
    Tl, Cl = Tl[0, 0], Cl[0, 0]
    for _ in range(3):
        fields = dict(T2=Tl, T=Tl, Ci=Cl)
        Tl, fresh = overlap.overlapped_step(kern, fields, sc, ("T",), ("x", "y"))
    return Tl[None, None]

f = shard_map(steps, mesh=mesh, in_specs=(P("x","y"), P("x","y")),
              out_specs=P("x","y"), check_vma=False)
got = halo.local_to_global(list(np.asarray(f(Ts, Cs)).reshape(4, *ls)), (2, 2))
err = float(np.max(np.abs(got - np.asarray(Tr))))
print("MULTISTEP_ERR", err)
assert err < 1e-6
""")
    assert "MULTISTEP_ERR" in out


def test_deep_halo_temporal_blocking():
    """One radius=k*r exchange + k fused local steps must reproduce the
    single-device k-step solution on the owned interiors (the distributed
    face of temporal blocking: k x fewer messages)."""
    out = run_subprocess("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from jax import shard_map
from repro.core import init_parallel_stencil, fd3d as fd
from repro.distributed import halo, overlap
from repro.launch.mesh import make_mesh

K = 3  # temporal block depth; ghost width = K * radius
mesh = make_mesh((2, 2), ("x", "y"))
Ni, Nz = 24, 10
Ng = Ni + 2 * K  # global array with K-wide physical boundary ring
rng = np.random.RandomState(0)
Tg = jnp.asarray(rng.rand(Ng, Ng, Nz), jnp.float32)
Cig = jnp.asarray(rng.rand(Ng, Ng, Nz) + 0.5, jnp.float32)
sc = dict(lam=1.0, dt=1e-4, _dx=1.0, _dy=1.0, _dz=1.0)

ps = init_parallel_stencil(backend="jnp", ndims=3)
@ps.parallel(outputs=("T2",), rotations={"T2": "T"})
def kern(T2, T, Ci, lam, dt, _dx, _dy, _dz):
    return {"T2": fd.inn(T) + dt*(lam*fd.inn(Ci)*(fd.d2_xi(T)*_dx**2
            + fd.d2_yi(T)*_dy**2 + fd.d2_zi(T)*_dz**2))}

# single-device reference: K rotated steps
a, b = Tg, Tg
for _ in range(K):
    a = kern(T2=a, T=b, Ci=Cig, **sc)
    a, b = b, a
Tr = b

lT = halo.global_to_local(Tg, (2, 2), radius=K)
lC = halo.global_to_local(Cig, (2, 2), radius=K)
ls = lT[0].shape
Ts = jnp.asarray(np.stack(lT).reshape(2, 2, *ls))
Cs = jnp.asarray(np.stack(lC).reshape(2, 2, *ls))

def steps(Tl, Cl):
    Tl, Cl = Tl[0, 0], Cl[0, 0]
    fields = dict(T2=Tl, T=Tl, Ci=Cl)
    out, _ = overlap.multi_step(kern, fields, sc, ("T",), ("x", "y"), K)
    return out[None, None]

f = shard_map(steps, mesh=mesh, in_specs=(P("x","y"), P("x","y")),
              out_specs=P("x","y"), check_vma=False)
got = halo.local_to_global(list(np.asarray(f(Ts, Cs)).reshape(4, *ls)),
                           (2, 2), radius=K)
# owned interiors (depth >= K from the global ring) must match exactly
err = float(np.max(np.abs(np.asarray(got)[K:-K, K:-K]
                          - np.asarray(Tr)[K:-K, K:-K])))
print("DEEP_HALO_ERR", err)
assert err < 1e-6
""")
    assert "DEEP_HALO_ERR" in out


def test_periodic_halo_wraps():
    out = run_subprocess("""
import jax, jax.numpy as jnp, numpy as np
from jax import shard_map
from jax.sharding import PartitionSpec as P
from repro.distributed import halo
from repro.launch.mesh import make_mesh
mesh = make_mesh((4,), ("x",))
n_local = 6
full = jnp.arange(4 * (n_local - 2), dtype=jnp.float32) + 100
locs = [jnp.pad(full[i*(n_local-2):(i+1)*(n_local-2)], (1, 1)) for i in range(4)]
arr = jnp.stack(locs)
def fn(a):
    return halo.halo_exchange(a[0], ("x",), radius=1, periodic=True)[None]
f = shard_map(fn, mesh=mesh, in_specs=P("x"), out_specs=P("x"), check_vma=False)
out = np.asarray(f(arr))
# rank 0 low ghost must equal the LAST interior value (wrap)
assert out[0, 0] == float(full[-1]), (out[0, 0], float(full[-1]))
assert out[3, -1] == float(full[0])
print("PERIODIC_OK")
""")
    assert "PERIODIC_OK" in out


def test_compressed_psum_and_error_feedback():
    out = run_subprocess("""
import jax, jax.numpy as jnp, numpy as np
from jax import shard_map
from jax.sharding import PartitionSpec as P
from repro.distributed import compression
from repro.launch.mesh import make_mesh
mesh = make_mesh((4,), ("pod",))
rng = np.random.RandomState(1)
g = jnp.asarray(rng.randn(4, 1000), jnp.float32)
def f(gl, err):
    red, new_err = compression.compressed_psum(gl[0], "pod", err[0])
    return red[None], new_err[None]
fn = shard_map(f, mesh=mesh, in_specs=(P("pod"), P("pod")),
               out_specs=(P("pod"), P("pod")), check_vma=False)
exact = jnp.sum(g, 0)
err = jnp.zeros_like(g)
red, err = fn(g, err)
rel = float(jnp.max(jnp.abs(red[0] - exact)) / jnp.max(jnp.abs(exact)))
assert rel < 0.05, rel
# error feedback: residual is carried, bias shrinks over repeats
accum = jnp.zeros_like(exact)
err = jnp.zeros_like(g)
for _ in range(50):
    red, err = fn(g, err)
    accum = accum + red[0]
bias = float(jnp.max(jnp.abs(accum / 50 - exact)))
assert bias < 0.02 * float(jnp.max(jnp.abs(exact))), bias
print("COMPRESS_OK", rel)
""")
    assert "COMPRESS_OK" in out


def test_elastic_restore_across_meshes():
    out = run_subprocess("""
import jax, jax.numpy as jnp, numpy as np, tempfile
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.checkpoint import CheckpointManager
from repro.launch.mesh import make_mesh
tree = {"w": jnp.arange(64, dtype=jnp.float32).reshape(8, 8),
        "b": jnp.arange(8, dtype=jnp.float32)}
with tempfile.TemporaryDirectory() as td:
    m1 = make_mesh((4, 2), ("data", "model"))
    t1 = jax.tree.map(lambda x: jax.device_put(
        x, NamedSharding(m1, P("data") if x.ndim == 1 else P("data", "model"))), tree)
    mgr = CheckpointManager(td)
    mgr.save(1, t1)
    # restore on a DIFFERENT mesh shape
    m2 = make_mesh((2, 4), ("data", "model"))
    sh2 = jax.tree.map(lambda x: NamedSharding(
        m2, P("model") if x.ndim == 1 else P("model", "data")), tree)
    like = jax.tree.map(lambda x: jnp.zeros_like(x), tree)
    restored, extra = mgr.restore(like, shardings=sh2)
    for k in tree:
        np.testing.assert_array_equal(np.asarray(restored[k]), np.asarray(tree[k]))
        assert restored[k].sharding.mesh.shape == m2.shape
print("ELASTIC_OK")
""")
    assert "ELASTIC_OK" in out


def test_global_local_roundtrip(rng):
    """global_to_local / local_to_global are exact inverses (any radius)."""
    import jax.numpy as jnp
    from repro.distributed import halo
    for radius, factors in [(1, (2, 2)), (2, (2, 4)), (1, (4, 1))]:
        inner = (8 * factors[0], 8 * factors[1])
        g = rng.rand(inner[0] + 2 * radius, inner[1] + 2 * radius, 5)
        locs = halo.global_to_local(jnp.asarray(g, jnp.float32), factors,
                                    radius=radius)
        back = halo.local_to_global(locs, factors, radius=radius)
        np.testing.assert_array_equal(back, np.float32(g))


def test_halo_radius2_overlap():
    """Radius-2 stencils (4th-order FD) exchange 2-wide halos and overlap
    bitwise like radius-1."""
    out = run_subprocess("""
import jax, jax.numpy as jnp, numpy as np
from jax import shard_map
from jax.sharding import PartitionSpec as P
from repro.core import init_parallel_stencil
from repro.distributed import halo, overlap
from repro.launch.mesh import make_mesh

mesh = make_mesh((4,), ("x",))
ps = init_parallel_stencil(backend="jnp", ndims=2)

@ps.parallel(outputs=("U2",), radius=2)
def kern(U2, U, dt):
    # 4th-order laplacian in x (radius 2), 2nd order in y
    d4 = (-U[4:, 2:-2] + 16*U[3:-1, 2:-2] - 30*U[2:-2, 2:-2]
          + 16*U[1:-3, 2:-2] - U[:-4, 2:-2]) / 12.0
    d2 = U[2:-2, 3:-1] - 2*U[2:-2, 2:-2] + U[2:-2, 1:-3]
    return {"U2": U[2:-2, 2:-2] + dt * (d4 + d2)}

rng = np.random.RandomState(0)
Ng = 4 * 16 + 4   # interior 64, radius 2
Ug = jnp.asarray(rng.rand(Ng, 20), jnp.float32)
want = kern(U2=Ug, U=Ug, dt=1e-3)

locs = halo.global_to_local(Ug, (4,), radius=2)
ls = locs[0].shape
Us = jnp.asarray(np.stack(locs))
sc = dict(dt=1e-3)

def step(Ul):
    Ul = Ul[0]
    fields = dict(U2=Ul, U=Ul)
    seq, _ = overlap.sequential_step(kern, fields, sc, ("U",), ("x",))
    ovl, _ = overlap.overlapped_step(kern, fields, sc, ("U",), ("x",))
    return seq[None], ovl[None]

f = shard_map(step, mesh=mesh, in_specs=P("x"), out_specs=(P("x"), P("x")),
              check_vma=False)
seq, ovl = f(Us)
assert (np.asarray(seq) == np.asarray(ovl)).all(), "overlap != sequential"
got = halo.local_to_global(list(np.asarray(seq)), (4,), radius=2)
err = float(np.max(np.abs(got - np.asarray(want))))
assert err < 1e-6, err
print("RADIUS2_OK", err)
""")
    assert "RADIUS2_OK" in out


def test_grouped_exchange_matches_per_field():
    """One-message-per-direction grouped halo exchange must be value-
    identical to per-field exchanges, for float and int fields, periodic
    and not, and a coupled multi-output kernel must step correctly on
    grouped-fresh fields."""
    out = run_subprocess("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from jax import shard_map
from repro.distributed import halo
from repro.launch.mesh import make_mesh

mesh = make_mesh((2, 2), ("x", "y"))
rng = np.random.RandomState(0)
ls = (12, 14, 6)
A = jnp.asarray(rng.rand(2, 2, *ls), jnp.float32)
B = jnp.asarray(rng.rand(2, 2, *ls), jnp.float32)
C = jnp.asarray(rng.randint(0, 100, (2, 2, *ls)), jnp.int32)

def f(A, B, C):
    fields = dict(A=A[0, 0], B=B[0, 0], C=C[0, 0])
    diffs = []
    for per in (False, True):
        g = halo.exchange_many(fields, ("A", "B", "C"), ("x", "y"),
                               radius=2, periodic=per, grouped=True)
        s = halo.exchange_many(fields, ("A", "B", "C"), ("x", "y"),
                               radius=2, periodic=per, grouped=False)
        for n in ("A", "B", "C"):
            diffs.append(jnp.max(jnp.abs((g[n] - s[n]).astype(jnp.float32))))
    return jnp.stack(diffs).max()[None, None]

g = shard_map(f, mesh=mesh, in_specs=(P("x","y"), P("x","y"), P("x","y")),
              out_specs=P("x","y"), check_vma=False)
d = float(np.max(np.asarray(g(A, B, C))))
assert d == 0.0, d
print("GROUPED_OK", d)
""")
    assert "GROUPED_OK" in out


def test_overlapped_step_coupled_staggered_inputs():
    """Coupled multi-output kernel with face-centered INPUT fields under
    @hide_communication: overlapped == sequential exchange-then-update,
    and the offset-aware face slabs keep the staggering contract."""
    out = run_subprocess("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from jax import shard_map
from repro.core import init_parallel_stencil, fd2d as fd
from repro.distributed import overlap
from repro.launch.mesh import make_mesh

mesh = make_mesh((4,), ("x",))
rng = np.random.RandomState(0)
ls = (18, 16)   # local (with ghosts); qx staggered along decomposed axis x
phi = jnp.asarray(rng.rand(4, *ls), jnp.float32)
Pe = jnp.asarray(rng.rand(4, *ls), jnp.float32)
qx = jnp.asarray(rng.rand(4, ls[0] - 1, ls[1]), jnp.float32)
qy = jnp.asarray(rng.rand(4, ls[0], ls[1] - 1), jnp.float32)

ps = init_parallel_stencil(backend="jnp", ndims=2)
@ps.parallel(outputs=("phi2", "Pe2"))
def kern(phi2, Pe2, phi, Pe, qx, qy, dtau):
    div_q = fd.d_xa(qx[:, 1:-1]) + fd.d_ya(qy[1:-1, :])
    Pe_new = fd.inn(Pe) + dtau * (-(div_q + fd.inn(Pe)))
    phi_new = fd.inn(phi) + dtau * (-(1.0 - fd.inn(phi)) * Pe_new)
    return {"phi2": phi_new, "Pe2": Pe_new}

sc = dict(dtau=0.01)

def f(phi, Pe, qx, qy):
    fields = dict(phi2=phi[0], Pe2=Pe[0], phi=phi[0], Pe=Pe[0],
                  qx=qx[0], qy=qy[0])
    seq, _ = overlap.sequential_step(kern, fields, sc, ("phi", "Pe"), ("x",))
    ovl, _ = overlap.overlapped_step(kern, fields, sc, ("phi", "Pe"), ("x",))
    d = jnp.maximum(jnp.max(jnp.abs(seq["phi2"] - ovl["phi2"])),
                    jnp.max(jnp.abs(seq["Pe2"] - ovl["Pe2"])))
    return d[None]

g = shard_map(f, mesh=mesh, in_specs=(P("x"),) * 4, out_specs=P("x"),
              check_vma=False)
d = float(np.max(np.asarray(g(phi, Pe, qx, qy))))
assert d == 0.0, d
print("COUPLED_OVERLAP_OK", d)
""")
    assert "COUPLED_OVERLAP_OK" in out


def test_exchange_depths_tighten_traffic():
    """Footprint-tightened exchange: refreshing only each field's
    inferred per-axis/per-side read depth yields kernel results identical
    to the full-radius exchange on the owned cells (the unread outer
    ghost layers may stay stale — the stencil never touches them)."""
    out = run_subprocess("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from jax import shard_map
from repro.core import init_parallel_stencil
from repro.distributed import halo, overlap
from repro.launch.mesh import make_mesh

mesh = make_mesh((4,), ("x",))
rng = np.random.RandomState(0)
R = 2                        # allocated ghost width
ls = (16 + 2 * R, 12)
U = jnp.asarray(rng.rand(4, *ls), jnp.float32)

ps = init_parallel_stencil(backend="jnp", ndims=2)
@ps.parallel(outputs=("U2",))
def kern(U2, U, dt):
    # one-sided in x: reads only U[i-2..i] -> depth (2, 0) on x
    return {"U2": U[2:-2, 1:-1] + dt * (U[:-4, 1:-1] - U[2:-2, 1:-1])}

ir = kern.stencil_ir(U2=ls, U=ls, dt=0.0)
assert ir.field_halo["U"] == ((2, 0), (0, 0)), ir.field_halo
sc = dict(dt=1e-3)

def f(Ul):
    Ul = Ul[0]
    full = halo.exchange_many(dict(U=Ul), ("U",), ("x",), radius=R)
    tight = halo.exchange_many(dict(U=Ul), ("U",), ("x",), radius=R,
                               depths={"U": ir.field_halo["U"][:1]})
    a = kern(U2=full["U"], U=full["U"], **sc)
    b = kern(U2=tight["U"], U=tight["U"], **sc)
    # owned cells (inside the ghost ring) must agree exactly
    d = jnp.max(jnp.abs(a[R:-R] - b[R:-R]))
    # sequential_step picks the tightened depths up automatically
    seq_full, _ = overlap.sequential_step(kern, dict(U2=Ul, U=Ul), sc,
                                          ("U",), ("x",))
    d2 = jnp.max(jnp.abs(a[R:-R] - seq_full[R:-R]))
    return jnp.maximum(d, d2)[None]

g = shard_map(f, mesh=mesh, in_specs=(P("x"),), out_specs=P("x"),
              check_vma=False)
d = float(np.max(np.asarray(g(U))))
assert d == 0.0, d
print("DEPTHS_OK", d)
""")
    assert "DEPTHS_OK" in out
