"""Causal depthwise conv1d as a Pallas stencil (Mamba2's short convolution).

This is the paper's technique applied to an LM architecture: the causal
short-conv in every Mamba2 block *is* a 1-D stencil with a one-sided halo
of width K-1, so it runs through the exact same machinery as the PDE
kernels — halo-extended VMEM windows over the sequence axis,
with a validity mask standing in for the zero left-padding.

x: (B, L, C), w: (K, C) depthwise taps, optional bias (C,).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _halo_window_spec(block, halo, index_map) -> pl.BlockSpec:
    """Halo-extended window with out-of-bounds padding (element offsets
    in the padded coordinates). Mosaic refuses padded windows, so this
    kernel runs in interpret mode only."""
    return pl.BlockSpec(
        tuple(pl.Element(b + lo + hi, padding=(lo, hi))
              for b, (lo, hi) in zip(block, halo)), index_map)


def _body(x_ref, w_ref, b_ref, o_ref, *, K, BL, silu):
    j = pl.program_id(1)
    xw = x_ref[...][0]  # (BL + K - 1, C) halo-extended window
    w = w_ref[...]      # (K, C)
    acc = jnp.zeros((BL, xw.shape[1]), jnp.float32)
    # out[t] = sum_d w[d] * x[t-d]; local slice for lag d starts at K-1-d.
    t = j * BL + jax.lax.broadcasted_iota(jnp.int32, (BL, 1), 0)
    for d in range(K):
        xs = xw[K - 1 - d : K - 1 - d + BL].astype(jnp.float32)
        valid = (t - d) >= 0  # zero left-padding instead of garbage OOB halo
        acc = acc + jnp.where(valid, xs, 0.0) * w[d].astype(jnp.float32)
    acc = acc + b_ref[...].astype(jnp.float32)
    if silu:
        acc = acc * jax.nn.sigmoid(acc)
    o_ref[...] = acc[None].astype(o_ref.dtype)


@functools.lru_cache(maxsize=64)
def _build(B, L, C, K, BL, dtype_name, silu, interpret):
    if not interpret:
        raise NotImplementedError(
            "conv1d_causal reads a left-padded halo window, which Mosaic "
            "refuses (padded Element windows); compiled for the TPU use "
            "ops.conv1d_causal(impl='chunked') (the jnp realization)")
    dtype = jnp.dtype(dtype_name)
    body = functools.partial(_body, K=K, BL=BL, silu=silu)
    return pl.pallas_call(
        body,
        grid=(B, L // BL),
        in_specs=[
            _halo_window_spec((1, BL, C), ((0, 0), (K - 1, 0), (0, 0)),
                             lambda b, j: (b, j * BL, 0)),
            pl.BlockSpec((K, C), lambda b, j: (0, 0)),
            pl.BlockSpec((C,), lambda b, j: (0,)),
        ],
        out_specs=pl.BlockSpec((1, BL, C), lambda b, j: (b, j, 0)),
        out_shape=jax.ShapeDtypeStruct((B, L, C), dtype),
        interpret=interpret,
    )


def conv1d_causal(x, w, b=None, silu: bool = False, block_l: int | None = None,
                  interpret: bool | None = None):
    """Fused causal depthwise conv (+ optional SiLU). Returns (B, L, C)."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    B, L, C = x.shape
    K = w.shape[0]
    if b is None:
        b = jnp.zeros((C,), x.dtype)
    if block_l is None:
        block_l = min(L, 512)
        while L % block_l:
            block_l //= 2
        block_l = max(block_l, 1)
    call = _build(B, L, C, K, int(block_l), x.dtype.name, bool(silu), bool(interpret))
    return call(x, w.astype(x.dtype), b.astype(x.dtype))
