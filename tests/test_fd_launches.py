"""Per-operator Pallas parity: every ``core.fd`` operator, written as the
whole right-hand side of a one-output ``@parallel`` update, gives on the
Pallas backend (interpret mode) what it gives on the jnp backend.

The Pallas launch's windows and halos come from the stencil IR's read
window of each operator (``ir/trace.py``), so a window inferred too
narrow shows here as a wrong value next to a block edge. 3-D operators
run twice: in an all-parallel launch whose ``tile`` cuts axis 0 into
three blocks, so block edges fall inside every window, and in a launch
streamed along axis 0 (``march_axis=0``), which a field staggered on
axis 0 refuses with a ``ValueError``. 2-D operators run in the launch the
engine derives, the grid in one block. A last case checks that the matrix
names every operator ``core.fd`` defines.

Each output field is laid out as ParallelStencil lays it: an operator
that shortens an axis by one writes a field staggered there (``@all``);
one that shortens it by two writes the interior of a collocated field
(``@inn``), whose boundary ring keeps its previous values.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import fd2d, fd3d, init_parallel_stencil

SHAPE3 = (24, 8, 128)
TILE3 = (8, 8, 128)      # three blocks along axis 0
MARCH_TILE3 = (4, 8, 128)
SHAPE2 = (24, 128)
INV_SPACING = (1.0, 0.5, 0.75)
# maxloc calls jnp.maximum, which the footprint tracer cannot follow: it
# runs in the declared-radius geometry, as examples/stokes3d.py runs it.
RADIUS = {"maxloc": 1}

OPS3 = ([f"{op}_{ax}{kind}" for op in ("d", "d2", "av")
         for ax in "xyz" for kind in "ai"]
        + [f"av_{axes}{kind}" for axes in ("xy", "xz", "yz") for kind in "ai"]
        + ["av", "inn", "maxloc", "laplacian"])
OPS2 = ([f"{op}_{ax}{kind}" for op in ("d", "d2", "av")
         for ax in "xy" for kind in "ai"]
        + ["av", "inn", "maxloc", "laplacian"])


def _apply(fd, op, A, inv):
    if op == "laplacian":
        return fd.laplacian(A, inv)
    return getattr(fd, op)(A)


def _out_shape(fd, op, shape):
    """An axis the operator shortens by one is staggered; by two, written
    on its interior."""
    got = _apply(fd, op, jnp.zeros(shape), INV_SPACING[:len(shape)]).shape
    return tuple(s - (s - g) % 2 for s, g in zip(shape, got))


def _run(fd, op, backend, shape, rng, **launch):
    ps = init_parallel_stencil(backend=backend, ndims=len(shape))

    @ps.parallel(outputs=("B",), radius=RADIUS.get(op), **launch)
    def kern(B, A, _dx, _dy, _dz):
        return {"B": _apply(fd, op, A, (_dx, _dy, _dz)[:len(shape)])}

    A = jnp.asarray(rng.rand(*shape), jnp.float32)
    B = jnp.asarray(rng.rand(*_out_shape(fd, op, shape)), jnp.float32)
    sc = dict(zip(("_dx", "_dy", "_dz"), INV_SPACING))
    return kern, np.asarray(kern(B=B, A=A, **sc))


def _parity(fd, op, shape, seed, **launch):
    want = _run(fd, op, "jnp", shape, np.random.RandomState(seed))[1]
    kern, got = _run(fd, op, "pallas", shape, np.random.RandomState(seed),
                     **launch)
    assert got.shape == want.shape
    np.testing.assert_allclose(got, want, atol=1e-6)
    (info,) = kern.launch_info.values()
    return info


@pytest.mark.parametrize("op", OPS3)
def test_fd3d_operator_tiled_launch(op):
    info = _parity(fd3d, op, SHAPE3, 3, tile=TILE3)
    assert info["march_axis"] is None and info["grid"][0] >= 3


@pytest.mark.parametrize("op", OPS3)
def test_fd3d_operator_streamed_launch(op):
    if _out_shape(fd3d, op, SHAPE3)[0] != SHAPE3[0]:
        with pytest.raises(ValueError, match="staggered axis"):
            _run(fd3d, op, "pallas", SHAPE3, np.random.RandomState(4),
                 tile=MARCH_TILE3, march_axis=0)
        return
    info = _parity(fd3d, op, SHAPE3, 4, tile=MARCH_TILE3, march_axis=0)
    assert info["march_axis"] == 0


@pytest.mark.parametrize("op", OPS2)
def test_fd2d_operator_whole_launch(op):
    info = _parity(fd2d, op, SHAPE2, 2)
    assert info["grid"] == (1, 1)


def test_matrix_names_every_operator():
    """A new ``core.fd`` operator joins the matrix above."""
    for fd, ops in ((fd3d, OPS3), (fd2d, OPS2)):
        public = {n for n in dir(fd)
                  if not n.startswith("_") and callable(getattr(fd, n))}
        assert sorted(ops) == sorted(public)
