"""The hand-written Fig. 1 kernel: Pallas (interpret) vs the ref.py oracle."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref


# --------------------------------------------------------------------------
# diffusion3d (paper Fig. 1 kernel)
# --------------------------------------------------------------------------
@pytest.mark.parametrize("shape", [(16, 16, 16), (32, 24, 40), (8, 8, 128)])
def test_diffusion3d_pallas_vs_ref(shape, rng):
    T = jnp.asarray(rng.rand(*shape), jnp.float32)
    T2 = T.copy()
    Ci = jnp.asarray(rng.rand(*shape) + 0.5, jnp.float32)
    args = (1.0, 1e-4, float(shape[0] - 1), float(shape[1] - 1),
            float(shape[2] - 1))
    want = ref.diffusion3d_step(T2, T, Ci, *args)
    got = ops.diffusion3d_step(T2, T, Ci, *args, impl="pallas")
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)


def test_diffusion3d_boundary_preserved(rng):
    shape = (16, 16, 16)
    T = jnp.asarray(rng.rand(*shape), jnp.float32)
    T2 = jnp.full(shape, 7.0, jnp.float32)
    got = ops.diffusion3d_step(T2, T, jnp.ones(shape), 1.0, 1e-4, 15.0, 15.0,
                               15.0, impl="pallas")
    # boundary cells must keep T2's values (the paper's @inn semantics)
    np.testing.assert_array_equal(np.asarray(got[0]), 7.0)
    np.testing.assert_array_equal(np.asarray(got[-1]), 7.0)
    np.testing.assert_array_equal(np.asarray(got[:, 0]), 7.0)
    np.testing.assert_array_equal(np.asarray(got[:, :, -1]), 7.0)
