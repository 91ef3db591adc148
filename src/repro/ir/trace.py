"""Trace a stencil update function into a :class:`StencilIR`.

One abstract evaluation with :mod:`.sym` window objects yields, per
output field, the expression graph plus everything the engine used to
take from the hand-declared ``radius``:

  * per-output **write geometry** — per-axis ``all``/``inn`` mode and
    interior-ring depth, derived from the traced update's shape exactly
    the way the backends derive it from concrete updates;
  * per-(output, field) **read intervals** relative to the write
    position;
  * per-field **exchange depths** (``field_halo``) — how deep a rank's
    ghost layers must be refreshed per axis and side;
  * the coupled system's **window halo** (``halo``) — the per-axis
    (lo, hi) VMEM window extension that makes every read of every output
    land inside the fetched windows, staggering included;
  * the equivalent scalar ``inferred_radius`` used to cross-check an
    (optional) user-declared ``radius``.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Mapping, Sequence

from . import sym
from .reductions import Reduction, check_operand_shapes, normalize_reductions
from .sym import SymArray, TraceError

__all__ = ["StencilIR", "trace_stencil"]


@dataclasses.dataclass(frozen=True)
class StencilIR:
    """Symbolic description of one fused stencil launch."""

    base_shape: tuple[int, ...]
    field_shapes: dict[str, tuple[int, ...]]
    offsets: dict[str, tuple[int, ...]]           # staggering vs base_shape
    out_names: tuple[str, ...]
    out_shapes: dict[str, tuple[int, ...]]        # traced update extents
    write_modes: dict[str, tuple[str, ...]]       # 'all' | 'inn' per axis
    write_rings: dict[str, tuple[int, ...]]       # interior-ring depth w
    reads_rel: dict[str, dict[str, tuple[tuple[int, int], ...]]]
    field_halo: dict[str, tuple[tuple[int, int], ...]]
    halo: tuple[tuple[int, int], ...]             # system window halo
    inferred_radius: int
    exprs: dict[str, SymArray] = dataclasses.field(repr=False, default_factory=dict)
    reductions: dict[str, Reduction] = dataclasses.field(default_factory=dict)
    red_exprs: dict[str, SymArray] = dataclasses.field(repr=False, default_factory=dict)

    @property
    def ndim(self) -> int:
        return len(self.base_shape)

    @property
    def read_fields(self) -> tuple[str, ...]:
        """Fields actually read by the update (HBM read set)."""
        return tuple(
            f for f in self.field_shapes
            if any(f in r for r in self.reads_rel.values())
        )

    def io_counts(self) -> tuple[int, int]:
        """(n_read, n_write): the paper's A_eff field counting, derived
        instead of hand-supplied."""
        return len(self.read_fields), len(self.out_names)

    @property
    def check_read_fields(self) -> tuple[str, ...]:
        """Fields a SEPARATE check pass would have to re-read from HBM:
        every reduction operand (outputs were just written, inputs were
        just read — a post-pass pays for both again). The fused epilogue
        reads none of them a second time; this set prices the traffic
        the fusion eliminates."""
        seen: list[str] = []
        for r in self.reductions.values():
            for op in r.operands:
                if op not in seen:
                    seen.append(op)
        return tuple(seen)

    def check_io_bytes(self, itemsize: int,
                       field_itemsizes=None) -> int:
        """HBM bytes of one separate (unfused) check pass: each operand
        field streams in once (at its own storage width when
        ``field_itemsizes`` — a ``{field: itemsize}`` mapping — is
        given). The fused epilogue's extra traffic is the per-tile
        partials write — O(n_blocks), negligible — so this is the
        per-check saving of ``reductions=``."""
        import math

        isz = field_itemsizes or {}
        return sum(math.prod(self.field_shapes[f]) * isz.get(f, itemsize)
                   for f in self.check_read_fields)

    def io_bytes(self, itemsize: int, field_itemsizes=None) -> int:
        """Exact bytes that must cross HBM per step under perfect reuse:
        every read field streams in once, every output streams out once
        (staggered fields at their own, smaller extents; mixed-precision
        fields at their own storage width via ``field_itemsizes``, a
        ``{field: itemsize}`` mapping defaulting to ``itemsize``)."""
        import math

        isz = field_itemsizes or {}
        total = 0
        for f in self.read_fields:
            total += math.prod(self.field_shapes[f]) * isz.get(f, itemsize)
        for o in self.out_names:
            total += math.prod(self.field_shapes[o]) * isz.get(o, itemsize)
        return total

    def describe(self) -> str:
        """Human-readable footprint table (README/CI smoke surface)."""
        lines = [f"base shape {self.base_shape}, "
                 f"inferred radius {self.inferred_radius}, "
                 f"window halo {self.halo}"]
        for o in self.out_names:
            lines.append(
                f"  out {o}: modes {self.write_modes[o]} "
                f"rings {self.write_rings[o]}"
            )
            for f, iv in sorted(self.reads_rel[o].items()):
                lines.append(f"    reads {f}: {iv}")
        for f, d in sorted(self.field_halo.items()):
            if any(x or y for x, y in d):
                lines.append(f"  exchange depth {f}: {d}")
        for n, r in sorted(self.reductions.items()):
            lines.append(f"  reduction {n}: {r.describe()}")
        return "\n".join(lines)


def _write_geometry(update_shape, field_shape, off, name):
    """Per-axis (mode, ring) from the traced update's extent — the SAME
    rule the backends apply to concrete updates (one shared
    implementation; on full arrays the 'window' is the field itself)."""
    from ..kernels.stencil import write_geometry

    return write_geometry(update_shape, field_shape, off, name, ring=None)


def trace_stencil(
    update_fn: Callable[[Mapping[str, SymArray], Mapping[str, object]], Mapping],
    field_shapes: Mapping[str, Sequence[int]],
    out_names: Sequence[str],
    scalar_names: Sequence[str] = (),
    reductions: Mapping[str, object] | None = None,
) -> StencilIR:
    """Abstractly evaluate ``update_fn(fields, scalars)`` once.

    ``field_shapes`` are the concrete per-field extents (staggered fields
    shorter than the base along their face axes). Scalars are passed as
    the neutral value 1.0 — value-dependent control flow inside an update
    function is untraceable by design (it would not be a stencil).

    ``reductions`` declares the launch's fused reduction epilogues
    (``{name: Reduction | "kind(field[, other])"}``): operands are
    validated against the field set (the operands of one reduction share
    one shape; each reduces over its own, possibly staggered, extent) and each
    check's elementwise map is traced into ``red_exprs`` — the cost
    model then prices check flops exactly and check *traffic* at what a
    separate pass would pay (``check_io_bytes``).

    Raises :class:`TraceError` for untraceable constructs and plain
    ``ValueError`` for genuinely invalid kernels (bad write extents,
    interior writes on staggered axes, reduction operands of different
    shapes).
    """
    shapes = {n: tuple(int(x) for x in s) for n, s in field_shapes.items()}
    if not shapes:
        raise TraceError("no fields to trace")
    nd = len(next(iter(shapes.values())))
    base = tuple(max(s[a] for s in shapes.values()) for a in range(nd))
    offsets = {n: tuple(b - x for b, x in zip(base, s))
               for n, s in shapes.items()}
    out_names = tuple(out_names)
    for o in out_names:
        if o not in shapes:
            raise TraceError(f"output {o!r} is not a field")

    leaves = {n: sym.field(n, s) for n, s in shapes.items()}
    scalars = {n: 1.0 for n in scalar_names}
    try:
        updates = update_fn(leaves, scalars)
    except (TraceError, ValueError):
        raise
    except Exception as e:  # jnp.* on SymArray, numpy coercion, ...
        raise TraceError(
            f"update function is not symbolically traceable ({type(e).__name__}: "
            f"{e}); declare radius= explicitly to use the legacy geometry"
        ) from e
    missing = set(out_names) - set(updates)
    if missing:
        raise ValueError(f"update_fn did not produce outputs {sorted(missing)}")

    out_shapes, write_modes, write_rings, reads_rel = {}, {}, {}, {}
    for o in out_names:
        u = updates[o]
        if not isinstance(u, SymArray):
            raise TraceError(
                f"output {o!r} update is {type(u).__name__}, not a traced "
                "stencil expression"
            )
        modes, rings = _write_geometry(u.shape, shapes[o], offsets[o], o)
        out_shapes[o] = u.shape
        write_modes[o], write_rings[o] = modes, rings
        reads_rel[o] = {
            f: tuple((lo - w, hi - w) for (lo, hi), w in zip(iv, rings))
            for f, iv in u.reads.items()
        }

    field_halo = {n: ((0, 0),) * nd for n in shapes}
    halo = [(0, 0)] * nd
    for o in out_names:
        for f, iv in reads_rel[o].items():
            fh = list(field_halo[f])
            for a, (lo, hi) in enumerate(iv):
                fh[a] = (max(fh[a][0], -lo), max(fh[a][1], hi))
                halo[a] = (
                    max(halo[a][0], -lo),
                    max(halo[a][1], hi + offsets[f][a]),
                )
            field_halo[f] = tuple(fh)
    # A staggered output must have its whole block frame covered by the
    # update: the window needs at least `off + w` extra cells on the high
    # side even when the kernel's *reads* are shallower (update extent
    # on a window is B - off + lo + hi - 2w, placed w cells in; covering
    # the B-cell out frame needs hi >= off + w, with w = 0 for `all`).
    # A collocated output's ring is the launch's concern (its window
    # extends to the write rings), not the data footprint's.
    for o in out_names:
        for a, off_a in enumerate(offsets[o]):
            if off_a:
                halo[a] = (halo[a][0],
                           max(halo[a][1], off_a + write_rings[o][a]))
    halo = tuple((max(lo, 0), max(hi, 0)) for lo, hi in halo)
    field_halo = {
        n: tuple((max(lo, 0), max(hi, 0)) for lo, hi in d)
        for n, d in field_halo.items()
    }
    r_inf = 0
    for lo, hi in halo:
        r_inf = max(r_inf, lo, hi)
    for rings in write_rings.values():
        r_inf = max(r_inf, *rings)

    reds = normalize_reductions(reductions, tuple(shapes))
    red_exprs: dict[str, SymArray] = {}
    for name, r in reds.items():
        check_operand_shapes(name, r, shapes)
        ops = [sym.field(op, shapes[op]) for op in r.operands]
        red_exprs[name] = r.map_element(*ops)

    return StencilIR(
        base_shape=base,
        field_shapes=shapes,
        offsets=offsets,
        out_names=out_names,
        out_shapes=out_shapes,
        write_modes=write_modes,
        write_rings=write_rings,
        reads_rel=reads_rel,
        field_halo=field_halo,
        halo=halo,
        inferred_radius=r_inf,
        exprs={o: updates[o] for o in out_names},
        reductions=reds,
        red_exprs=red_exprs,
    )
