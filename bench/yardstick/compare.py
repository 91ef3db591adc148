"""The comparison that decides ``correct``: the program's answers against
the plain reference, replayed from the same seed after the window.

Every number compared is a gap that reads 0 when the two agree:

* ``fields_rel_err``: over the compared fields (the newest time level,
  and the one before where the solver keeps two),
  ``max |program - reference| / max |reference|``, the worst field. One
  step more or fewer moves a field by the size of one step's change;
* ``step_rel_err``: for a solver that keeps two time levels (the
  reference's ``INCREMENTS``, pairs of newest and previous), the last
  step's change, newest less previous, against the reference's:
  ``max |dP - dR| / max |dR|``, the worst pair. Levels returned swapped
  read 2;
* ``check_rel_err``: the gap of the solve's fused check value from the
  reference's, over the reference's;
* ``iters_gap``: the most steps by which any solve of the window stopped
  apart from the reference's solve (an exact comparison, limit 0).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

CHUNK = 100   # reference steps per dispatched program


class Reference:
    """The plain reference of one configuration and seed at ``dtype``, its
    state laid out over ``sharding``; each jitted program is built once."""

    def __init__(self, ref, cfg, p, dtype=jnp.float32, sharding=None):
        self.ref, self.cfg, self.p = ref, cfg, p
        self.dtype, self.sharding = jnp.dtype(dtype), sharding
        self.shape = tuple(cfg["grid"])
        self._programs: dict = {}

    def program(self, k: int):
        """``k`` steps as one jitted program, its state donated."""
        if k not in self._programs:
            ref = self.ref

            def run(state, p):
                return jax.lax.fori_loop(0, k, lambda _, s: ref.step(s, p),
                                         state)

            self._programs[k] = jax.jit(run, donate_argnums=0)
        return self._programs[k]

    def initial(self) -> dict:
        state = self.ref.initial(self.cfg, self.p, self.shape, self.sharding)
        if self.dtype != jnp.float32:
            state = {k: v.astype(self.dtype) for k, v in state.items()}
        return state

    def steps(self, state: dict, n: int) -> dict:
        """``n`` steps from ``state`` (donated)."""
        while n:
            k = CHUNK if n >= CHUNK else 1
            state = self.program(k)(state, self.p)
            n -= k
        return state

    def replay(self, nsteps: int) -> dict:
        """The state after ``nsteps`` steps from the seed's initial state."""
        return self.steps(self.initial(), nsteps)

    def solve(self, traffic: dict):
        """A solve to tolerance: steps, and every ``check_every`` steps the
        check value; stops at the first check at or under ``tol``.
        Returns (state, iters, check value)."""
        state = self.initial()
        every, tol = int(traffic["check_every"]), float(traffic["tol"])
        if "check" not in self._programs:
            self._programs["check"] = jax.jit(self.ref.check_value)
        it, err = 0, float("inf")
        while err > tol and it < int(traffic["max_iters"]):
            state = self.steps(state, every)
            it += every
            err = float(self._programs["check"](state))
        return state, it, err


@jax.jit
def _rel_gap(a, b):
    a = a.astype(jnp.float32)
    b = b.astype(jnp.float32)
    return jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b))


def fields_rel_err(program: dict, reference: dict, region=None) -> float:
    """The worst field's ``max |program - reference| / max |reference|``.
    ``region`` slices the reference first (the program gave only those
    cells)."""
    worst = 0.0
    for name, got in program.items():
        want = reference[name]
        if region is not None:
            want = want[region]
        if tuple(got.shape) != tuple(want.shape):
            raise ValueError(f"{name}: program gave {got.shape}, the "
                             f"reference {want.shape}")
        worst = max(worst, float(_rel_gap(got, want)))
    return worst


@jax.jit
def _rel_step_gap(new, old, rnew, rold):
    f32 = jnp.float32
    d = new.astype(f32) - old.astype(f32)
    rd = rnew.astype(f32) - rold.astype(f32)
    return jnp.max(jnp.abs(d - rd)) / jnp.max(jnp.abs(rd))


def step_rel_err(program: dict, reference: dict, pairs, region=None) -> float:
    """The worst pair's ``max |dP - dR| / max |dR|``, where ``d`` is a
    pair's newest level less its previous one."""
    worst = 0.0
    for new, old in pairs:
        rnew, rold = reference[new], reference[old]
        if region is not None:
            rnew, rold = rnew[region], rold[region]
        worst = max(worst, float(_rel_step_gap(program[new], program[old],
                                               rnew, rold)))
    return worst


def state_numbers(ref, program: dict, reference: dict, region=None) -> dict:
    """The numbers every cell compares on its final state."""
    out = {"fields_rel_err": fields_rel_err(program, reference, region)}
    if ref.INCREMENTS:
        out["step_rel_err"] = step_rel_err(program, reference,
                                           ref.INCREMENTS, region)
    return out


def judge(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """Each number against its limit: (all within, {name: {value, limit}}).
    A number that is not finite, or has no limit, fails."""
    out, ok = {}, True
    for name, value in numbers.items():
        lim = limits.get(name)
        fine = (lim is not None and value == value
                and abs(value) != float("inf") and value <= lim)
        ok = ok and fine
        out[name] = {"value": value, "limit": lim}
    return ok, out
