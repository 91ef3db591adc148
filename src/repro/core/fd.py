"""Math-close finite-difference operators (ParallelStencil.FiniteDifferences{1,2,3}D).

These are the JAX analogues of the paper's macros (``@inn``, ``@d2_xi``,
``@av`` ...). They are *relative* slice expressions, so the very same kernel
source works on

  * full arrays (the ``jnp`` / array-programming backend), and
  * halo-extended VMEM windows inside a Pallas kernel body (the ``pallas``
    backend),

which is how the single-source xPU property of ParallelStencil is realized
here (DESIGN.md C1/C2).

Naming follows ParallelStencil:
  ``*_a``  operate over the full extent of the differentiated axis,
  ``*_i``  additionally restrict all *other* axes to their interior,
  ``inn``  selects the interior in all axes;
  ``av_xya``/``av_xyi`` (and ``xz``, ``yz``; 3-D) average the 4 cells
  around an edge over two axes (the shear-stress averages of a
  staggered Stokes grid).

All operators reduce the differentiated axis length by their stencil width;
combined with ``inn``-style selection the results of e.g. ``d2_xi``,
``d2_yi``, ``d2_zi`` share one common shape — exactly the interior.
"""
from __future__ import annotations

import numpy as np

__all__ = ["fd1d", "fd2d", "fd3d", "FiniteDifferences"]


def _s(ndim: int, axis: int, sl: slice, other: slice) -> tuple[slice, ...]:
    return tuple(sl if a == axis else other for a in range(ndim))


_FULL = slice(None)
_INN = slice(1, -1)


class FiniteDifferences:
    """Finite-difference operator namespace for a fixed dimensionality.

    Instantiated once per ndim below (``fd1d``, ``fd2d``, ``fd3d``); all
    methods are static-like (take the array as first argument).
    """

    def __init__(self, ndim: int):
        self.ndim = ndim
        ax_names = "xyz"[:ndim]
        # Generate the full ParallelStencil-style API surface: d_xa, d_xi,
        # d2_xa, d2_xi, av_xa, av_xi, ... per axis.
        for axis, name in enumerate(ax_names):
            setattr(self, f"d_{name}a", self._make(self._d, axis, inner_other=False))
            setattr(self, f"d_{name}i", self._make(self._d, axis, inner_other=True))
            setattr(self, f"d2_{name}a", self._make(self._d2, axis, inner_other=False))
            setattr(self, f"d2_{name}i", self._make(self._d2, axis, inner_other=True))
            setattr(self, f"av_{name}a", self._make(self._av, axis, inner_other=False))
            setattr(self, f"av_{name}i", self._make(self._av, axis, inner_other=True))
        # Two-axis averages (3-D: av_xya, av_xyi, av_xza, ...): the mean of
        # the 4 cells around an edge; `_i` takes the remaining axis's interior.
        if ndim == 3:
            for a, b in ((0, 1), (0, 2), (1, 2)):
                nm = ax_names[a] + ax_names[b]
                setattr(self, f"av_{nm}a", self._make_av2(a, b, inner_other=False))
                setattr(self, f"av_{nm}i", self._make_av2(a, b, inner_other=True))

    # -- primitive stencils ------------------------------------------------
    def _d(self, A, axis, other):
        n = self.ndim
        return A[_s(n, axis, slice(1, None), other)] - A[_s(n, axis, slice(None, -1), other)]

    def _d2(self, A, axis, other):
        n = self.ndim
        return (
            A[_s(n, axis, slice(2, None), other)]
            - 2.0 * A[_s(n, axis, _INN, other)]
            + A[_s(n, axis, slice(None, -2), other)]
        )

    def _av(self, A, axis, other):
        n = self.ndim
        return 0.5 * (
            A[_s(n, axis, slice(1, None), other)] + A[_s(n, axis, slice(None, -1), other)]
        )

    def _make(self, op, axis, inner_other):
        other = _INN if inner_other else _FULL
        def f(A):
            return op(A, axis, other)
        f.__name__ = f"{op.__name__}_ax{axis}_{'i' if inner_other else 'a'}"
        return f

    def _make_av2(self, a, b, inner_other):
        """ParallelStencil's ``@av_xyi``-style average over axes ``a`` and
        ``b``: ``(A[i,j] + A[i+1,j] + A[i,j+1] + A[i+1,j+1]) * 0.25``, summed
        in that order."""
        other = _INN if inner_other else _FULL

        def corner(da, db):
            return tuple(slice(da, None if da else -1) if ax == a
                         else slice(db, None if db else -1) if ax == b
                         else other for ax in range(self.ndim))

        def f(A):
            return (A[corner(0, 0)] + A[corner(1, 0)] + A[corner(0, 1)]
                    + A[corner(1, 1)]) * 0.25
        f.__name__ = f"_av2_ax{a}{b}_{'i' if inner_other else 'a'}"
        return f

    # -- interior / neighborhood ops ---------------------------------------
    def inn(self, A):
        """Interior of A in every axis (the paper's ``@inn``)."""
        return A[(_INN,) * self.ndim]

    def av(self, A):
        """Average over the 2^ndim cell corners (the paper's ``@av``)."""
        out = A
        for axis in range(self.ndim):
            out = 0.5 * (
                out[_s(self.ndim, axis, slice(1, None), _FULL)]
                + out[_s(self.ndim, axis, slice(None, -1), _FULL)]
            )
        return out

    def maxloc(self, A):
        """Maximum over the 3^ndim neighborhood, evaluated on the interior
        (the paper/package's ``@maxloc``)."""
        import jax.numpy as jnp

        n = self.ndim
        out = None
        for offs in np.ndindex(*(3,) * n):
            sl = tuple(slice(o, None if o == 2 else o - 2) for o in offs)
            v = A[sl]
            out = v if out is None else jnp.maximum(out, v)
        return out

    def laplacian(self, A, inv_spacing):
        """Sum of second differences on the interior, scaled by 1/d^2."""
        names = "xyz"[: self.ndim]
        total = 0.0
        for axis, nm in enumerate(names):
            total = total + getattr(self, f"d2_{nm}i")(A) * inv_spacing[axis] ** 2
        return total


fd1d = FiniteDifferences(1)
fd2d = FiniteDifferences(2)
fd3d = FiniteDifferences(3)
