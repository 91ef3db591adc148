"""Named reduction epilogues fused into stencil launches.

The paper's headline solvers are *iterative*: pseudo-transient and
explicit steppers that check ``err = max|dT|`` (or an L2 residual / a
conserved quantity) every few sweeps. A separate whole-array norm pass
re-reads the operand fields — for the 2-field diffusion check it roughly
doubles the memory traffic of a check step — and the host round-trip on
the result serializes the step loop. A :class:`Reduction` instead rides
*inside* the launch: each grid tile folds its domain-masked partial into
a tiny per-tile partials output while the updated block is still in
VMEM/registers, and a scalar combine over the partials finishes the
value — no second HBM pass, no host sync.

Kinds (all elementwise-map then associative-combine):

  * ``max_abs(F)``          — ``max |F|``            (residual / stability)
  * ``max_abs_diff(F, G)``  — ``max |F - G|``        (convergence check)
  * ``sum(F)``              — ``sum F``              (conserved quantity)
  * ``sum_sq(F)``           — ``sum F^2``            (L2 norm sq. / mass)
  * ``finite(F)``           — ``max 1[!isfinite F]`` (health guard: 0 iff
    every element is finite, 1 as soon as any NaN/Inf appears)
  * ``nan_count(F)``        — ``sum 1[!isfinite F]`` (how many cells blew up)

The ``finite``/``nan_count`` kinds fold a *non-finite indicator* — the
elementwise map turns NaN/Inf into exactly ``1.0`` and everything else
into ``0.0`` BEFORE the combine, so (unlike a raw ``max``) the folded
scalar is NaN-free and safe to branch on inside a ``lax.while_loop``.
They are the device-resident numerical health guard of the serving
layer (``repro.serve`` quarantines samples whose guard goes positive),
but work standalone like any other kind.

Operands name *fields of the launch*: an output operand reduces the
freshly written values, an input operand the current (boundary-source)
values — e.g. ``max_abs_diff(T2, T)`` is exactly ``max|T2_new - T|``.
An operand may be staggered: it reduces over its own extent (the
per-tile domain masks bound each block's cells by the operand's shape,
so the base-extent padding a staggered output is stored with never
enters the fold). The operands of one reduction share one shape.

Cross-program caveat (the reassociation rule): reductions reassociate,
so the fused value is *bitwise* reproducible only within one compiled
program. Comparisons against a separately compiled post-pass (or the
other backend) must use ``allclose`` tolerances, never equality.
"""
from __future__ import annotations

import dataclasses
from typing import Mapping, Sequence

__all__ = ["Reduction", "check_operand_shapes", "normalize_reductions",
           "REDUCTION_KINDS"]

# kind -> (arity, combine): combine is "max" or "sum" (both associative
# and commutative — the partials may be folded in any tile order).
REDUCTION_KINDS = {
    "max_abs": (1, "max"),
    "max_abs_diff": (2, "max"),
    "sum": (1, "sum"),
    "sum_sq": (1, "sum"),
    "finite": (1, "max"),       # max of the non-finite indicator
    "nan_count": (1, "sum"),    # count of non-finite cells
}

# kinds whose elementwise map is the non-finite indicator
_INDICATOR_KINDS = ("finite", "nan_count")


@dataclasses.dataclass(frozen=True)
class Reduction:
    """One named reduction: ``kind`` over ``field`` (and ``other``)."""

    kind: str
    field: str
    other: str | None = None

    def __post_init__(self):
        if self.kind not in REDUCTION_KINDS:
            raise ValueError(
                f"reduction kind {self.kind!r} must be one of "
                f"{tuple(REDUCTION_KINDS)}"
            )
        arity, _ = REDUCTION_KINDS[self.kind]
        if arity == 2 and self.other is None:
            raise ValueError(
                f"reduction {self.kind!r} takes two operands, e.g. "
                f"Reduction('{self.kind}', 'T2', 'T')"
            )
        if arity == 1 and self.other is not None:
            raise ValueError(
                f"reduction {self.kind!r} takes one operand; got second "
                f"operand {self.other!r}"
            )

    @property
    def operands(self) -> tuple[str, ...]:
        return (self.field,) if self.other is None else (self.field,
                                                         self.other)

    @property
    def combine(self) -> str:
        return REDUCTION_KINDS[self.kind][1]

    # -- realizations -------------------------------------------------------
    def map_element(self, x, y=None):
        """The elementwise pre-combine map. Works on concrete arrays AND
        on :class:`..ir.sym.SymArray` windows (abs/sub/mul only), so the
        IR can trace the check expression for flop/byte accounting with
        the same code the backends execute."""
        if self.kind == "max_abs":
            return abs(x)
        if self.kind == "max_abs_diff":
            return abs(x - y)
        if self.kind == "sum":
            return x
        if self.kind in _INDICATOR_KINDS:
            # Non-finite indicator: 1.0 where NaN/Inf, else 0.0. On the
            # symbolic trace the indicator costs one compare-class op per
            # element with the operand's own footprint — modeled as the
            # |.|-node (same reads, adds-class flop) since SymArray has
            # no isfinite.
            if hasattr(x, "flop_kind"):      # SymArray (IR trace)
                return abs(x)
            import jax.numpy as jnp

            return (~jnp.isfinite(x)).astype(
                x.dtype if jnp.issubdtype(x.dtype, jnp.floating)
                else jnp.float32)
        return x * x  # sum_sq

    def fold(self, mapped, mask=None):
        """Fold one tile's mapped values into a scalar partial. Cells
        outside ``mask`` contribute the neutral element (0 works for both
        combines here: the max kinds fold |.| >= 0)."""
        import jax.numpy as jnp

        if mask is not None:
            mapped = jnp.where(mask, mapped, jnp.zeros_like(mapped))
        return jnp.max(mapped) if self.combine == "max" else jnp.sum(mapped)

    def finish(self, partials):
        """Combine per-tile partials into the launch's scalar."""
        import jax.numpy as jnp

        return (jnp.max(partials) if self.combine == "max"
                else jnp.sum(partials))

    def all_reduce(self, value, mesh_axes):
        """Finish across ranks: ONE pmax/psum over the rank partials
        (rank-local fused values ARE valid partials — the combines are
        associative)."""
        import jax

        axes = tuple(mesh_axes)
        return (jax.lax.pmax(value, axes) if self.combine == "max"
                else jax.lax.psum(value, axes))

    def describe(self) -> str:
        return (f"{self.kind}({self.field})" if self.other is None
                else f"{self.kind}({self.field}, {self.other})")


def _parse(spec: str) -> Reduction:
    """``"max_abs_diff(T2, T)"``-style compact form."""
    s = spec.strip()
    if "(" not in s or not s.endswith(")"):
        raise ValueError(
            f"cannot parse reduction spec {spec!r}; expected "
            "'kind(field)' or 'kind(field, other)'"
        )
    kind, rest = s.split("(", 1)
    ops = [p.strip() for p in rest[:-1].split(",") if p.strip()]
    if not 1 <= len(ops) <= 2:
        raise ValueError(f"reduction spec {spec!r} needs 1 or 2 operands")
    return Reduction(kind.strip(), ops[0],
                     ops[1] if len(ops) == 2 else None)


def normalize_reductions(
    reductions: Mapping[str, object] | None,
    field_names: Sequence[str] | None = None,
) -> dict[str, Reduction]:
    """Normalize ``{name: Reduction | "kind(field[, other])"}``. With
    ``field_names`` the operands are validated against the launch's
    field set (call sites that know it yet — the decorator does not)."""
    out: dict[str, Reduction] = {}
    for name, spec in (reductions or {}).items():
        r = spec if isinstance(spec, Reduction) else _parse(str(spec))
        if field_names is not None:
            for op in r.operands:
                if op not in field_names:
                    raise ValueError(
                        f"reduction {name!r} = {r.describe()} reads "
                        f"{op!r}, which is not a field of this launch "
                        f"(fields: {tuple(field_names)})"
                    )
        out[str(name)] = r
    return out


def check_operand_shapes(name: str, r: Reduction,
                         shapes: Mapping[str, Sequence[int]]) -> None:
    """The operands of one reduction must share one shape (each reduces
    over its own extent; a difference of two staggerings has none)."""
    got = {op: tuple(shapes[op]) for op in r.operands}
    if len(set(got.values())) > 1:
        raise ValueError(
            f"reduction {name!r} = {r.describe()} reads operands of "
            f"different staggered shapes {got}; the operands of one "
            "reduction must share one shape")
