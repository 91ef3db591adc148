"""Halo exchange for block domain decomposition (ImplicitGlobalGrid.jl, C6).

The global grid is distributed over a device mesh with `shard_map`; every
rank owns a local array that carries ``radius`` ghost layers per face.
``halo_exchange`` refreshes those ghost layers from the face-adjacent
neighbors with ``jax.lax.ppermute`` — one permute per (axis, direction),
exactly the neighbor pattern ImplicitGlobalGrid drives through MPI.

Non-periodic boundaries: ranks at the domain edge keep their existing ghost
values (which hold the physical boundary condition); periodic boundaries
wrap the permutation instead.

All functions here are *rank-local* (must run inside `shard_map`).
"""
from __future__ import annotations

from typing import Mapping, Sequence

import math

import jax
import jax.numpy as jnp
from jax import lax

from .. import telemetry as _telemetry


# Wire formats for ghost payloads. Interiors can stay f32 while the
# ppermute payload ships narrower: "bf16" casts around the permute
# (exactly representable halves of the mantissa survive; error is one
# bf16 rounding, ~3 decimal digits), "int8" block-quantizes via
# ``distributed.compression`` (error <= scale/2 = max|payload|/254 per
# block — guarded, not exact; see README "Mixed precision"). Payloads
# already at (or below) the wire width, and non-float payloads, pass
# through uncompressed — compression never widens a message.
COMPRESS_MODES = (None, "bf16", "int8")


def _check_compress(compress):
    if compress not in COMPRESS_MODES:
        raise ValueError(
            f"compress={compress!r} is not one of {COMPRESS_MODES}")


def _permute(payload, mesh_ax, perm, compress):
    """``lax.ppermute`` with an optionally compressed wire format. The
    result is cast back to the payload dtype, so call sites are wire-
    format agnostic."""
    dt = payload.dtype
    is_float = jnp.issubdtype(dt, jnp.floating)
    if compress == "bf16" and is_float and dt.itemsize > 2:
        return lax.ppermute(payload.astype(jnp.bfloat16), mesh_ax,
                            perm).astype(dt)
    if compress == "int8" and is_float and dt.itemsize > 1:
        from . import compression as _comp

        q, scale, meta = _comp.quantize_int8(payload)
        q = lax.ppermute(q, mesh_ax, perm)
        scale = lax.ppermute(scale, mesh_ax, perm)
        return _comp.dequantize_int8(q, scale, meta).astype(dt)
    return lax.ppermute(payload, mesh_ax, perm)


def _slab(arr, axis: int, start: int, size: int):
    idx = [slice(None)] * arr.ndim
    idx[axis] = slice(start, start + size) if start >= 0 else slice(start, start + size or None)
    return arr[tuple(idx)]


def _axis_depths(depths, radius: int, n_axes: int):
    """Normalize per-axis exchange depths: None -> full radius; an int
    broadcasts; entries are (lo, hi) pairs or ints, clamped to the ghost
    width (the allocation contract stays ``radius`` layers)."""
    if depths is None:
        return [(radius, radius)] * n_axes
    if isinstance(depths, int):
        depths = [depths] * n_axes
    depths = list(depths)
    if len(depths) != n_axes:
        raise ValueError(
            f"exchange depths {depths} cover {len(depths)} axes but "
            f"{n_axes} array axes are decomposed — a short list would "
            "silently skip the trailing axes' ghost refresh"
        )
    out = []
    for d in depths:
        lo, hi = (d, d) if isinstance(d, int) else (int(d[0]), int(d[1]))
        if lo > radius or hi > radius or lo < 0 or hi < 0:
            raise ValueError(
                f"exchange depth {(lo, hi)} outside the allocated ghost "
                f"width [0, radius={radius}]"
            )
        out.append((lo, hi))
    return out


def halo_exchange(
    local: jax.Array,
    mesh_axes: Sequence[str],
    array_axes: Sequence[int] | None = None,
    radius: int = 1,
    periodic: bool | Sequence[bool] = False,
    depths=None,
    compress: str | None = None,
) -> jax.Array:
    """Refresh ghost layers of ``local`` along each decomposed axis.

    Args:
      local: rank-local array with ``radius`` ghost layers on decomposed axes.
      mesh_axes: mesh axis name per decomposed array axis.
      array_axes: which array axes are decomposed (default: first len(mesh_axes)).
      radius: ghost width (the allocation).
      periodic: global wrap per axis (scalar broadcasts).
      depths: optional per-axis (lo, hi) *exchange* depths <= radius (the
        footprint-inferred read depths): only the innermost ``lo`` cells
        of the low ghost ring / ``hi`` of the high ring are refreshed, so
        a field the stencil reads one-sided (or not at all) moves fewer
        (or no) bytes. ``None`` refreshes the full ring.
      compress: optional wire format for the ghost payload — ``"bf16"``
        (cast around the permute, 2 B/elt) or ``"int8"`` (block-
        quantized via ``distributed.compression``, ~1 B/elt). Ghosts
        land back at the array dtype; interiors are untouched. Single-
        rank self-wraps are local copies and stay exact.
    """
    _check_compress(compress)
    if array_axes is None:
        array_axes = list(range(len(mesh_axes)))
    if isinstance(periodic, bool):
        periodic = [periodic] * len(mesh_axes)
    r = radius
    depths = _axis_depths(depths, r, len(mesh_axes))
    for mesh_ax, arr_ax, per, (d_lo, d_hi) in zip(mesh_axes, array_axes,
                                                  periodic, depths):
        n = jax.lax.axis_size(mesh_ax)
        if n == 1:
            if per:
                # self-wrap: ghost layers come from own opposite interior
                if d_lo:
                    lo_src = _slab(local, arr_ax, -(r + d_lo), d_lo)
                    local = _set_slab(local, arr_ax, r - d_lo, lo_src)
                if d_hi:
                    hi_src = _slab(local, arr_ax, r, d_hi)
                    local = _set_slab(local, arr_ax, -r, hi_src)
            continue
        idx = lax.axis_index(mesh_ax)
        if d_lo:
            # --- my high interior slab -> right neighbor's low ghost ---
            send_hi = _slab(local, arr_ax, -(r + d_lo), d_lo)
            perm_r = [(i, i + 1) for i in range(n - 1)]
            if per:
                perm_r.append((n - 1, 0))
            recv_lo = _permute(send_hi, mesh_ax, perm_r, compress)
            has_left = (idx > 0) | (per and n > 1)
            cur_lo = _slab(local, arr_ax, r - d_lo, d_lo)
            local = _set_slab(local, arr_ax, r - d_lo,
                              jnp.where(has_left, recv_lo, cur_lo))
        if d_hi:
            # --- my low interior slab -> left neighbor's high ghost ---
            send_lo = _slab(local, arr_ax, r, d_hi)
            perm_l = [(i + 1, i) for i in range(n - 1)]
            if per:
                perm_l.append((0, n - 1))
            recv_hi = _permute(send_lo, mesh_ax, perm_l, compress)
            has_right = (idx < n - 1) | (per and n > 1)
            cur_hi = _slab(local, arr_ax, -r, d_hi)
            local = _set_slab(local, arr_ax, -r,
                              jnp.where(has_right, recv_hi, cur_hi))
    return local


def _set_slab(arr, axis: int, start: int, value):
    idx = [slice(None)] * arr.ndim
    if start >= 0:
        idx[axis] = slice(start, start + value.shape[axis])
    else:
        stop = start + value.shape[axis]
        idx[axis] = slice(start, stop if stop < 0 else None)
    return arr.at[tuple(idx)].set(value)


def _field_depths(depths, names, radius: int, n_axes: int) -> dict:
    """Normalize a per-field depth mapping (missing fields or None ->
    full radius; entries follow :func:`_axis_depths`)."""
    out = {}
    for f in names:
        d = None if depths is None else depths.get(f)
        out[f] = _axis_depths(d, radius, n_axes)
    return out


def grouped_halo_exchange(
    fields: Mapping[str, jax.Array],
    names: Sequence[str],
    mesh_axes: Sequence[str],
    array_axes: Sequence[int] | None = None,
    radius: int = 1,
    periodic: bool | Sequence[bool] = False,
    depths: Mapping[str, object] | None = None,
    compress: str | None = None,
) -> dict:
    """Refresh ghost layers of *all* ``names`` with ONE message per
    (axis, direction) round-trip instead of one per field.

    The per-field face slabs are flattened and concatenated into a single
    ``ppermute`` payload (per dtype group — mixed-precision systems send
    one message per dtype), then split and scattered back. For a coupled
    system of F fields this turns ``2 * ndim * F`` permutes into
    ``2 * ndim`` — the latency win ImplicitGlobalGrid gets from posting
    all of a system's MPI messages together. Mixed-shape staggered fields
    group fine: only the flattened slab sizes differ.

    ``depths`` (per field, per axis (lo, hi) <= radius — the footprint-
    inferred read depths) shrinks each field's slab to what the stencil
    actually reads; a field with depth 0 on a side contributes nothing to
    that direction's payload.

    ``compress`` selects the wire format of the whole concatenated
    payload (``"bf16"``/``"int8"``, see :func:`halo_exchange`): each
    (axis, direction, dtype-group) message is compressed once, so the
    per-message scale metadata of ``"int8"`` amortizes over every field
    riding in it.

    Values are identical to per-field :func:`halo_exchange` calls
    (with matching ``compress``, which quantizes per concatenated
    payload here vs per field there — both within the int8 error bound).
    """
    _check_compress(compress)
    if array_axes is None:
        array_axes = list(range(len(mesh_axes)))
    if isinstance(periodic, bool):
        periodic = [periodic] * len(mesh_axes)
    out = dict(fields)
    r = radius
    fdep = _field_depths(depths, names, r, len(mesh_axes))
    # dtype groups (ppermute payloads must be homogeneous)
    groups: dict = {}
    for n in names:
        groups.setdefault(jnp.asarray(out[n]).dtype, []).append(n)
    for ax_i, (mesh_ax, arr_ax, per) in enumerate(
            zip(mesh_axes, array_axes, periodic)):
        n_ranks = jax.lax.axis_size(mesh_ax)
        if n_ranks == 1:
            if per:
                for f in names:
                    d_lo, d_hi = fdep[f][ax_i]
                    if d_lo:
                        lo_src = _slab(out[f], arr_ax, -(r + d_lo), d_lo)
                        out[f] = _set_slab(out[f], arr_ax, r - d_lo, lo_src)
                    if d_hi:
                        hi_src = _slab(out[f], arr_ax, r, d_hi)
                        out[f] = _set_slab(out[f], arr_ax, -r, hi_src)
            continue
        idx = lax.axis_index(mesh_ax)
        perm_r = [(i, i + 1) for i in range(n_ranks - 1)]
        perm_l = [(i + 1, i) for i in range(n_ranks - 1)]
        if per:
            perm_r.append((n_ranks - 1, 0))
            perm_l.append((0, n_ranks - 1))
        has_left = (idx > 0) | (per and n_ranks > 1)
        has_right = (idx < n_ranks - 1) | (per and n_ranks > 1)
        for grp in groups.values():
            # --- high interior slabs -> right neighbors' low ghosts ---
            lo_grp = [f for f in grp if fdep[f][ax_i][0]]
            if lo_grp:
                send_hi = [
                    _slab(out[f], arr_ax, -(r + fdep[f][ax_i][0]),
                          fdep[f][ax_i][0]) for f in lo_grp
                ]
                recv = _permute(
                    jnp.concatenate([s.reshape(-1) for s in send_hi]),
                    mesh_ax, perm_r, compress)
                ofs = 0
                for f, s in zip(lo_grp, send_hi):
                    piece = recv[ofs:ofs + s.size].reshape(s.shape)
                    ofs += s.size
                    d_lo = fdep[f][ax_i][0]
                    cur = _slab(out[f], arr_ax, r - d_lo, d_lo)
                    out[f] = _set_slab(out[f], arr_ax, r - d_lo,
                                       jnp.where(has_left, piece, cur))
            # --- low interior slabs -> left neighbors' high ghosts ---
            hi_grp = [f for f in grp if fdep[f][ax_i][1]]
            if hi_grp:
                send_lo = [
                    _slab(out[f], arr_ax, r, fdep[f][ax_i][1])
                    for f in hi_grp
                ]
                recv = _permute(
                    jnp.concatenate([s.reshape(-1) for s in send_lo]),
                    mesh_ax, perm_l, compress)
                ofs = 0
                for f, s in zip(hi_grp, send_lo):
                    piece = recv[ofs:ofs + s.size].reshape(s.shape)
                    ofs += s.size
                    d_hi = fdep[f][ax_i][1]
                    cur = _slab(out[f], arr_ax, -r, d_hi)
                    out[f] = _set_slab(out[f], arr_ax, -r,
                                       jnp.where(has_right, piece, cur))
    return out


def _wire_bytes(n_elems: int, itemsize: int, is_float: bool,
                compress: str | None) -> int:
    """Wire bytes of one ppermute payload of ``n_elems`` homogeneous
    elements under a compressed wire format (mirrors :func:`_permute`:
    bf16 = 2 B/elt when narrowing applies; int8 = BLOCK-padded 1 B/elt
    plus one f32 scale per block)."""
    if compress == "bf16" and is_float and itemsize > 2:
        return n_elems * 2
    if compress == "int8" and is_float and itemsize > 1:
        from .compression import BLOCK

        n_blocks = -(-n_elems // BLOCK)
        return n_blocks * BLOCK + n_blocks * 4
    return n_elems * itemsize


def exchange_byte_counts(
    shapes: Mapping[str, Sequence[int]],
    itemsizes: Mapping[str, int],
    float_fields: Mapping[str, bool],
    n_axes: int,
    radius: int = 1,
    depths: Mapping[str, object] | None = None,
    compress: str | None = None,
    grouped: bool = True,
    active: Sequence[bool] | None = None,
    dtype_groups: Sequence[Sequence[str]] | None = None,
) -> dict:
    """Analytic per-rank payload bytes of ONE :func:`exchange_many` call.

    Pure host-side arithmetic over static shapes — safe to evaluate at
    trace time, which is where the telemetry instrumentation calls it
    (the counts are per exchange invocation; a solve taking N steps ships
    N times these bytes). Returns ``{"bytes_raw": ..., "bytes_wire":
    ..., "messages": ...}`` where *raw* prices every slab at its storage
    width and *wire* applies the compressed format per message
    (per dtype group when ``grouped``, per field otherwise).
    ``active`` masks axes whose mesh extent is 1 (no messages)."""
    names = list(shapes)
    fdep = _field_depths(depths, names, radius, n_axes)
    if active is None:
        active = [True] * n_axes
    if dtype_groups is None:
        if grouped:
            by_key: dict = {}
            for f in names:
                by_key.setdefault((itemsizes[f], float_fields[f]),
                                  []).append(f)
            dtype_groups = list(by_key.values())
        else:
            dtype_groups = [[f] for f in names]
    raw = wire = messages = 0
    for ax in range(n_axes):
        if not active[ax]:
            continue
        for side in (0, 1):
            for grp in dtype_groups:
                sent = [f for f in grp if fdep[f][ax][side]]
                if not sent:
                    continue
                elems = sum(
                    fdep[f][ax][side]
                    * math.prod(s for a, s in enumerate(shapes[f]) if a != ax)
                    for f in sent)
                isz = itemsizes[sent[0]]
                is_f = float_fields[sent[0]]
                raw += elems * isz
                wire += _wire_bytes(elems, isz, is_f, compress)
                messages += (2 if (compress == "int8" and is_f and isz > 1)
                             else 1)
    return {"bytes_raw": int(raw), "bytes_wire": int(wire),
            "messages": int(messages)}


def _emit_exchange_telemetry(col, fields, names, mesh_axes, radius, depths,
                             compress, grouped):
    """Trace-time byte accounting: fires once per compiled exchange
    geometry (shapes are static under the trace), never per step — the
    device program is untouched. Gauges carry per-exchange bytes;
    multiply by the step count for totals."""
    try:
        active = [jax.lax.axis_size(ax) > 1 for ax in mesh_axes]
    except Exception:       # outside shard_map — assume every axis ships
        active = None
    shp = {f: tuple(fields[f].shape) for f in names}
    isz = {f: jnp.asarray(fields[f]).dtype.itemsize for f in names}
    isf = {f: jnp.issubdtype(jnp.asarray(fields[f]).dtype, jnp.floating)
           for f in names}
    counts = exchange_byte_counts(shp, isz, isf, len(mesh_axes),
                                  radius=radius, depths=depths,
                                  compress=compress, grouped=grouped,
                                  active=active)
    col.event("halo.exchange_traced", fields=list(names), radius=radius,
              compress=compress, grouped=grouped, **counts)
    col.gauge("halo.bytes_raw_per_exchange", counts["bytes_raw"],
              compress=str(compress))
    col.gauge("halo.bytes_wire_per_exchange", counts["bytes_wire"],
              compress=str(compress))
    col.count("halo.traced_exchanges", 1)


def exchange_many(
    fields: Mapping[str, jax.Array],
    names: Sequence[str],
    mesh_axes: Sequence[str],
    radius: int = 1,
    periodic=False,
    grouped: bool = True,
    depths: Mapping[str, object] | None = None,
    compress: str | None = None,
) -> dict:
    """Refresh ghost layers of several fields. ``grouped=True`` (default)
    sends the whole field group per (axis, direction) in one ppermute
    (:func:`grouped_halo_exchange`); ``grouped=False`` keeps the
    one-permute-per-field reference path. ``depths`` tightens each
    field's exchanged slab to its inferred per-axis (lo, hi) read depth;
    ``compress`` selects the ghost wire format (``"bf16"``/``"int8"``,
    see :func:`halo_exchange`). The exchange's operations carry the
    ``halo.exchange`` name scope."""
    _check_compress(compress)
    col = _telemetry.get()
    if col.enabled:
        _emit_exchange_telemetry(col, fields, names, mesh_axes, radius,
                                 depths, compress, grouped)
    with jax.named_scope("halo.exchange"):
        if grouped:
            return grouped_halo_exchange(
                fields, names, mesh_axes, radius=radius, periodic=periodic,
                depths=depths, compress=compress)
        out = dict(fields)
        for n in names:
            out[n] = halo_exchange(
                out[n], mesh_axes, radius=radius, periodic=periodic,
                depths=None if depths is None else depths.get(n),
                compress=compress)
        return out


def global_to_local(global_arr, factors: Sequence[int], radius: int = 1):
    """Split a global array (with physical boundary layers) into per-rank
    local blocks with ghost layers, returned as a flat list in row-major
    rank order. Host-side utility for tests and initialization."""
    import numpy as np

    g = np.asarray(global_arr)
    r = radius
    inner = [s - 2 * r for s in g.shape[: len(factors)]]
    locals_ = []
    for ridx in np.ndindex(*factors):
        sl = []
        for ax, (i, f) in enumerate(zip(ridx, factors)):
            step = inner[ax] // f
            sl.append(slice(i * step, i * step + step + 2 * r))
        sl += [slice(None)] * (g.ndim - len(factors))
        locals_.append(g[tuple(sl)].copy())
    return locals_


def local_to_global(locals_, factors: Sequence[int], radius: int = 1):
    """Inverse of :func:`global_to_local` (interior stitching)."""
    import numpy as np

    r = radius
    sample = np.asarray(locals_[0])
    inner = [s - 2 * r for s in sample.shape[: len(factors)]]
    gshape = [i * f + 2 * r for i, f in zip(inner, factors)]
    gshape += list(sample.shape[len(factors):])
    g = np.zeros(gshape, sample.dtype)
    for rank, ridx in enumerate(np.ndindex(*factors)):
        loc = np.asarray(locals_[rank])
        dst, src = [], []
        for ax, (i, f) in enumerate(zip(ridx, factors)):
            step = inner[ax]
            lo_g = i * step + (0 if i == 0 else r)
            hi_g = (i + 1) * step + (2 * r if i == f - 1 else r)
            dst.append(slice(lo_g, hi_g))
            lo_l = 0 if i == 0 else r
            hi_l = loc.shape[ax] - (0 if i == f - 1 else r)
            src.append(slice(lo_l, hi_l))
        dst += [slice(None)] * (g.ndim - len(factors))
        src += [slice(None)] * (g.ndim - len(factors))
        g[tuple(dst)] = loc[tuple(src)]
    return g
