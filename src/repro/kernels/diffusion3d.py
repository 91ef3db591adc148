"""Hand-specialized Pallas kernel for the paper's Fig. 1 diffusion step.

This is the "explicit notation" variant of the solver (paper §3 compares
math-close vs explicit): the stencil is written with raw window slices
instead of the fd.* operators, and the kernel is tuned by hand (tile
override, fused scalar folding, all-parallel ``dimension_semantics``,
optional in-place ``input_output_aliases`` double-buffer rotation).
Numerically identical to ``ref.diffusion3d_step`` and to the math-close
kernel built through ``core.parallel`` — tests assert all three agree.
It shares the generic builder's window geometry (kernels/stencil.py):
clamped halo windows along the tiled axes, the minor axes whole.

``nsteps=k`` runs the temporally-blocked variant: the VMEM windows carry a
k-cell halo and the Euler update is swept k times per launch, so T/Ci cross
HBM once per k steps. The result is bitwise-identical to k rotated
single-step calls whenever T2 and T agree on the boundary ring (true for
the solvers: both buffers start as copies; boundaries are never updated).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import stencil as _stencil


def _body(scal_ref, T2_ref, T_ref, Ci_ref, o_ref, *stage, block, shape,
          nsteps, whalo, realign):
    lam, dt, idx2, idy2, idz2 = (scal_ref[i] for i in range(5))
    pids = tuple(pl.program_id(a) for a in range(3))
    origin = _stencil.frame_origin(pids, block, whalo, shape)
    T = T_ref[...]
    Ci = Ci_ref[...][1:-1, 1:-1, 1:-1]
    mask = _stencil._valid_mask(origin, T.shape, shape, (1, 1, 1),
                                ("inn",) * 3)
    for s in range(nsteps):
        c = T[1:-1, 1:-1, 1:-1]
        lap = (
            (T[2:, 1:-1, 1:-1] - 2 * c + T[:-2, 1:-1, 1:-1]) * idx2
            + (T[1:-1, 2:, 1:-1] - 2 * c + T[1:-1, :-2, 1:-1]) * idy2
            + (T[1:-1, 1:-1, 2:] - 2 * c + T[1:-1, 1:-1, :-2]) * idz2
        )
        upd = jnp.pad((c + dt * (lam * Ci * lap)).astype(T.dtype),
                      ((1, 1),) * 3)
        # Intermediate sweeps rotate in-register: the sweep's T2 becomes
        # the next sweep's T; globally-boundary cells keep their values.
        T = jnp.where(mask, upd, T if s < nsteps - 1 else T2_ref[...])
    if not stage:
        o_ref[...] = T[tuple(slice(0, b) for b in block)]
        return
    stage[0][...] = T
    o_ref[...] = stage[0][tuple(
        pl.ds(pids[a] * block[a] - origin[a], block[a]) if realign[a]
        else slice(0, block[a]) for a in range(3))]


@functools.lru_cache(maxsize=32)
def _build(shape, dtype_name, tile, interpret, nsteps, alias):
    dtype = jnp.dtype(dtype_name)
    grid, block = _stencil.derive_launch(
        shape, 1, 3, dtype.itemsize, tile=tile, nsteps=nsteps,
        tpu_layout=True, compiled=not interpret)
    whalo = _stencil.effective_halo(shape, block, nsteps)
    realign = tuple(lo + hi > 0 for lo, hi in whalo)
    fext = tuple(min(b + lo + hi, s)
                 for s, b, (lo, hi) in zip(shape, block, whalo))

    def win_map(*pids):
        return _stencil.frame_origin(pids, block, whalo, shape)

    body = functools.partial(_body, block=block, shape=shape, nsteps=nsteps,
                             whalo=whalo, realign=realign)
    kwargs = {}
    if any(realign):
        kwargs["scratch_shapes"] = [pltpu.VMEM(fext, dtype)]
    if alias:
        # input order: (scal, T2, T, Ci) -> donate T2's buffer to the output
        # so the double-buffer rotates in place instead of allocating.
        kwargs["input_output_aliases"] = {1: 0}
    if not interpret:
        kwargs["compiler_params"] = _stencil.compiler_params(3)
    return pl.pallas_call(
        body,
        grid=grid,
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM)]
        + [_stencil.window_spec(fext, win_map)] * 3,
        out_specs=pl.BlockSpec(block, lambda i, j, k: (i, j, k)),
        out_shape=jax.ShapeDtypeStruct(shape, dtype),
        interpret=interpret,
        **kwargs,
    )


def diffusion3d_step(T2, T, Ci, lam, dt, inv_dx, inv_dy, inv_dz,
                     tile=None, interpret=None, nsteps=1, alias=False):
    """Fused Pallas diffusion step(s); returns the temperature after
    ``nsteps`` explicit Euler steps as one full array (one launch).

    ``alias=True`` donates T2's buffer to the output (in-place rotation):
    the caller's T2 is invalid afterwards, on every backend.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    nsteps = int(nsteps)
    if nsteps < 1:
        raise ValueError(f"nsteps must be >= 1, got {nsteps}")
    dtype = T.dtype
    scal = jnp.array(
        [lam, dt, inv_dx**2, inv_dy**2, inv_dz**2], dtype=dtype
    )
    call = _build(tuple(T.shape), dtype.name, tile if tile is None else tuple(tile),
                  bool(interpret), nsteps, bool(alias))
    return call(scal, T2, T, Ci)
