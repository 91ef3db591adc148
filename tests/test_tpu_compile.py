"""Compile the engine's Pallas kernels for a TPU v5e that is described,
not attached: Mosaic refuses here, at no chip time, what interpret mode
never checks (block tiling, VMEM limits). Each test compiles at the
paper's Fig. 1 size, 512^3 f32 (the 2-D porosity step at 512^2), and
asserts the compiled program holds the Pallas kernel (``tpu_custom_call``).
Modes that cannot compile for the TPU by design are tested for their
``NotImplementedError``.

The topology is described inside module fixtures only: the TPU runtime
may be loaded by one process at a time, so describing it while a module
is imported would break the other test workers.
"""
import math
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import fd3d as fd, init_parallel_stencil
from repro.kernels import autotune, diffusion3d
from repro.kernels.stencil import TileNotCompilable

N = 512
SCALARS = dict(lam=1.0, dt=1e-6, _dx=1.0, _dy=1.0, _dz=1.0)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache off around them
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def fig1(dtype="float32", **kw):
    ps = init_parallel_stencil(backend="pallas", dtype=dtype, ndims=3,
                               interpret=False)

    @ps.parallel(outputs=("T2",), rotations={"T2": "T"}, **kw)
    def step(T2, T, Ci, lam, dt, _dx, _dy, _dz):
        return {"T2": fd.inn(T) + dt * (lam * fd.inn(Ci) * (
            fd.d2_xi(T) * _dx ** 2 + fd.d2_yi(T) * _dy ** 2
            + fd.d2_zi(T) * _dz ** 2))}

    return step


def compiled_text(fn, sharding, shape=(N,) * 3, dtype="float32", n=3):
    x = jax.ShapeDtypeStruct(shape, jnp.dtype(dtype), sharding=sharding)
    return jax.jit(fn).lower(*[x] * n).compile().as_text()


@pytest.mark.parametrize("case", [
    "step", "run_steps4", "reductions", "march_axis0", "bf16"])
def test_fig1_kernel_compiles_for_v5e(one_chip, case):
    dtype = "bfloat16" if case == "bf16" else "float32"
    kw = {"reductions": {"err": "max_abs_diff(T2, T)"}} \
        if case == "reductions" else \
        {"march_axis": 0} if case == "march_axis0" else {}
    step = fig1(dtype, **kw)
    k = 4 if case == "run_steps4" else 1

    def f(T2, T, Ci):
        return step.run_steps(k, T2=T2, T=T, Ci=Ci, **SCALARS)

    assert "tpu_custom_call" in compiled_text(f, one_chip, dtype=dtype)


@pytest.mark.parametrize("dtype,march,shape", [
    ("float32", None, (N,) * 3), ("bfloat16", None, (N,) * 3),
    ("float32", 0, (N,) * 3), ("float32", None, (N + 2,) * 3),
    ("float32", None, (256, 300, 200))])
def test_vmem_accounting_covers_mosaic(one_chip, monkeypatch, dtype, march,
                                       shape):
    """The preflight's footprint bounds what Mosaic really allocates: a
    launch compiled with its scoped-VMEM limit set to its own accounted
    footprint still compiles. (N+2)^3 is one rank of a 512^3-per-chip
    decomposition; its minor dims are padded to whole lane tiles. With
    ``march`` None the derivation chooses, and streams these shapes
    along axis 0."""
    step = fig1(dtype, march_axis=march)

    def f(T2, T, Ci):
        return step(T2=T2, T=T, Ci=Ci, **SCALARS)

    x = jax.ShapeDtypeStruct(shape, jnp.dtype(dtype))
    jax.eval_shape(f, x, x, x)
    (info,) = step.launch_info.values()
    monkeypatch.setenv("REPRO_VMEM_LIMIT_BYTES", str(info["vmem_bytes"]))
    step = fig1(dtype, march_axis=march)
    assert "tpu_custom_call" in compiled_text(f, one_chip, shape=shape,
                                              dtype=dtype)


@pytest.mark.parametrize("n", [N, N + 2])
def test_vmem_accounting_covers_mosaic_all_parallel(one_chip, monkeypatch,
                                                    n):
    """The same bound for the all-parallel launch an explicit tile pins
    (two-plane blocks, the derivation's choice before it streamed)."""
    tile = (2, n, n)

    def f(T2, T, Ci):
        return step(T2=T2, T=T, Ci=Ci, **SCALARS)

    step = fig1(tile=tile)
    x = jax.ShapeDtypeStruct((n,) * 3, jnp.float32)
    jax.eval_shape(f, x, x, x)
    (info,) = step.launch_info.values()
    assert info["march_axis"] is None and info["block"] == tile
    monkeypatch.setenv("REPRO_VMEM_LIMIT_BYTES", str(info["vmem_bytes"]))
    step = fig1(tile=tile)
    assert "tpu_custom_call" in compiled_text(f, one_chip, shape=(n,) * 3)


def _cell_launch(case):
    """The launch a benchmark cell builds, by default derivation: the
    kernel, the function of three fields that runs it, and the shape."""
    if case == "gp-fused":
        from examples import gross_pitaevskii as gp

        cfg = gp.GPConfig(n=N, backend="pallas", fused=True, interpret=False)
        step = gp.make_step(gp.make_grid(cfg), cfg)
        return (step.kernels[0],
                lambda re, im, V: step(re, im, 1e-3, V), (N,) * 3)
    kw = {"reductions": {"err": "max_abs_diff(T2, T)"}} \
        if case == "fig1-reductions" else {}
    step = fig1(**kw)
    return (step, lambda T2, T, Ci: step(T2=T2, T=T, Ci=Ci, **SCALARS),
            (N + 2,) * 3 if case == "rank-514" else (N,) * 3)


@pytest.mark.parametrize("case", ["fig1-reductions", "rank-514", "gp-fused"])
def test_cell_launches_stream_and_compile_for_v5e(one_chip, monkeypatch,
                                                  case):
    """The default-derived launches the benchmark's cells run — Fig. 1
    with its fused check (the solve cell), one 514^3 rank of the four-chip
    cell, GP's fused radius-2 update — stream along axis 0, compile, and
    their accounted VMEM bounds what Mosaic allocates (each compiles with
    its scoped-VMEM limit set to its own footprint)."""
    kern, f, shape = _cell_launch(case)
    x = jax.ShapeDtypeStruct(shape, jnp.float32)
    jax.eval_shape(f, x, x, x)
    (info,) = kern.launch_info.values()
    assert info["march_axis"] == 0 and info["block"][1:] == shape[1:]
    monkeypatch.setenv("REPRO_VMEM_LIMIT_BYTES", str(info["vmem_bytes"]))
    kern, f, shape = _cell_launch(case)
    assert "tpu_custom_call" in compiled_text(f, one_chip, shape=shape)


@pytest.mark.parametrize("march", [None, 0])
def test_autotune_candidates_compile_for_v5e(one_chip, march):
    """Every (tile, nsteps, march) launch ``autotune_diffusion3d`` would
    measure at 512^3 compiles: the tuner searches the TPU layout."""
    cands = autotune.launch_candidates((N,) * 3, 1, 3, 4, (1, 2, 4),
                                       march_candidates=(march,))
    assert len(cands) >= 2
    for tile, k, m in cands:
        step = fig1(tile=tile, march_axis=m)

        def f(T2, T, Ci):
            return step.run_steps(k, T2=T2, T=T, Ci=Ci, **SCALARS)

        assert "tpu_custom_call" in compiled_text(f, one_chip), (tile, k, m)


def test_hand_kernel_compiles_for_v5e(one_chip):
    def f(T2, T, Ci):
        return diffusion3d.diffusion3d_step(T2, T, Ci, 1.0, 1e-6, 1.0, 1.0,
                                            1.0, interpret=False)

    assert "tpu_custom_call" in compiled_text(f, one_chip)


def test_porosity_wave_step_compiles_for_v5e(one_chip):
    from examples import porosity_waves as pw

    cfg = pw.PorosityConfig(n=N, backend="pallas", interpret=False)
    step = pw.make_step(pw.make_grid(cfg), cfg)
    text = compiled_text(lambda phi, Pe: step(phi, Pe, 1e-3), one_chip,
                         shape=(N, N), n=2)
    assert "tpu_custom_call" in text


def test_modes_refused_for_the_tpu(one_chip):
    """Modes Mosaic cannot compile raise a pointed NotImplementedError
    (never an interpret-mode fallback)."""
    def run(step):
        return lambda T2, T, Ci: step(T2=T2, T=T, Ci=Ci, **SCALARS)

    with pytest.raises(NotImplementedError, match="minor axis"):
        compiled_text(run(fig1(march_axis=2)), one_chip)
    with pytest.raises(TileNotCompilable, match="minor axis"):
        compiled_text(run(fig1(tile=(8, 64, N))), one_chip)


def test_launch_carries_parallel_name(one_chip):
    """The compiled launch is named after the ``@parallel`` function and
    carries it in its ``kernel_metadata``, where a device trace finds it;
    the ``custom_call_target`` the benchmark matches is unchanged."""
    step = fig1()

    def f(T2, T, Ci):
        return step(T2=T2, T=T, Ci=Ci, **SCALARS)

    text = compiled_text(f, one_chip, shape=(64,) * 3)
    calls = re.findall(r'%(\S+) = [^\n]*custom_call_target="tpu_custom_call"'
                       r'[^\n]*kernel_metadata=\{\s*([^}]*)\}', text)
    assert len(calls) == 1
    name, meta = calls[0]
    assert re.sub(r"\.\d+$", "", name) == "step"
    assert meta.strip() == '"parallel":"step"'


STOKES_N = 383


def _stokes_launch(name):
    """One launch of the Stokes3D iteration at 383^3 cells, as the
    benchmark's cell builds it: the kernel and a function of its fields."""
    import inspect

    from examples import stokes3d as st

    cfg = st.StokesConfig(n=STOKES_N, backend="pallas", interpret=False)
    kern = {k.__name__: k for k in st.make_kernels(cfg)}[name]
    shapes = st.shapes(STOKES_N)
    params = list(inspect.signature(kern.fn).parameters)
    fields = [p for p in params if p in shapes]
    scalars = {p: 1.0 for p in params if p not in shapes}

    def f(*arrays, kernel=kern):
        return kernel(**dict(zip(fields, arrays)), **scalars)

    return kern, f, [shapes[p] for p in fields]


def _with_operand_shapes(compiled):
    """The compiled module's text with each operand's shape printed, as a
    device trace names an operation."""
    from jax._src.lib import xla_client

    opts = xla_client._xla.HloPrintOptions()
    opts.print_operand_shape = True
    return "\n".join(m.to_string(opts) for m in
                     compiled.runtime_executable().hlo_modules())


@pytest.mark.parametrize("name", ["compute_P", "compute_tau", "compute_dV",
                                  "compute_V"])
def test_stokes_launch_compiles_for_v5e_and_counts_its_bytes(one_chip, name):
    """Each launch of the Stokes3D cell compiles at 383^3 for the
    described v5e, on the refetching all-parallel path (a field staggered
    on axis 0 keeps it off the streamed one), and the device trace's
    reducer (``bench/yardstick/trace.py``) reads from its HLO the
    benchmark's byte count for it (``bench/references/stokes3d.py``) less
    what that count holds past each result's own extent (it counts results
    at the launch's base extent, where the launch stores them at their
    own): the plain launch exactly, the checked one plus its reductions'
    partials, one (8, 128) f32 tile per block."""
    import importlib.util
    import json

    from examples import stokes3d as st

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

    def load(rel, as_name):
        spec = importlib.util.spec_from_file_location(
            as_name, os.path.join(root, "bench", *rel.split("/")))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    ref = load("references/stokes3d.py", "bench_reference_stokes3d")
    trace = load("yardstick/trace.py", "bench_yardstick_trace")
    with open(os.path.join(root, "bench", "configs",
                           "stokes3d-383.json")) as f:
        cfg = json.load(f)
    checked, fn, shapes = _stokes_launch(name)
    args = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)
            for s in shapes]
    counted = []
    for kern in (checked.with_reductions(None), checked):
        compiled = jax.jit(lambda *a: fn(*a, kernel=kern)).lower(
            *args).compile()
        (info,) = kern.launch_info.values()
        assert info["march_axis"] is None and info["grid"][0] > 1
        lines = [ln for ln in _with_operand_shapes(compiled).splitlines()
                 if 'custom_call_target="tpu_custom_call"' in ln]
        assert len(lines) == 1
        counted.append(trace.launch_bytes(lines[0], {}))
    base = math.prod(max(e) for e in zip(*shapes))
    padding = sum(base - math.prod(st.shapes(STOKES_N)[o])
                  for o in checked.outputs)
    want = ref.launch_bytes(cfg)[name] - 4 * padding
    assert counted[0] == want
    partials = len(checked.reductions) * math.prod(info["grid"]) * 8 * 128 * 4
    assert counted[1] == want + partials


def test_stokes_iteration_stores_results_at_own_extent(one_chip):
    """The Stokes solver's 383^3 program (the ``while`` of four launches
    per iteration, checked every 10) compiled for the described v5e: each
    launch's field results have the fields' own shapes, and no ``slice``
    or ``copy`` takes one as its operand (the launch stores each staggered
    result at its own extent, nothing crops it after)."""
    from examples import stokes3d as st
    from repro.core import iterate

    cfg = st.StokesConfig(n=STOKES_N, backend="pallas", interpret=False)
    kernels = st.make_kernels(cfg)
    shapes = st.shapes(STOKES_N)
    solver = iterate.make_solver(kernels, st.scalars(cfg), check_every=10,
                                 error=st.residual_error(STOKES_N))
    state = {k: jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)
             for k, s in shapes.items()}
    text = _with_operand_shapes(
        jax.jit(solver).lower(state, 1e-6, 100).compile())
    ops = {}
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%(\S+) = (.+?) ([\w-]+)\((.*)", line)
        if m:
            ops[m.group(1)] = (m.group(3), m.group(2), m.group(4))
    calls = {n: typ for n, (op, typ, rest) in ops.items()
             if op == "custom-call" and "tpu_custom_call" in rest}
    names = {re.sub(r"\.\d+$", "", n) for n in calls}
    assert names == {"compute_P", "compute_tau", "compute_dV", "compute_V"}
    field = {f"f32[{','.join(map(str, s))}]" for s in shapes.values()}
    outputs = {k: {"f32[" + ",".join(map(str, shapes[o])) + "]"
                   for o in kern.outputs}
               for k, kern in ((k.__name__, k) for k in kernels)}
    results = set()
    for n, typ in calls.items():
        got = {t.split("{")[0] for t in re.findall(r"f32\[[\d,]*\]\{[^}]*\}",
                                                   typ)}
        # the field results, one per output at its own shape; the rest
        # are the reductions' per-block partials, rank 5
        assert {t for t in got if t.count(",") == 2} \
            == outputs[re.sub(r"\.\d+$", "", n)], (n, typ)
        results.add(n)
    for n, (op, typ, rest) in ops.items():
        if op == "get-tuple-element":
            src = re.search(r"%([^\s,)]+)", rest).group(1)
            if src in calls and typ.split("{")[0] in field:
                results.add(n)
    for n, (op, typ, rest) in ops.items():
        if op in ("slice", "copy"):
            # the operand list ends at the first ")," (a layout's
            # tiling, ``T(8,128)``, holds a ")" too)
            operands = set(re.findall(r"%([\w.-]+)", rest.split("), ")[0]))
            assert not operands & results, (n, op, rest)
