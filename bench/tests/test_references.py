"""The plain references against independent NumPy loops and against the
program at a small size on the CPU (Pallas in interpret mode)."""
import numpy as np
import pytest

import harness
from yardstick import compare


def _cfg(name, n):
    return harness.shrink(harness.load_json("configs", name), n)


def _np_fig1(T, T2, Ci, p, nsteps):
    ix, iy, iz = p["inv_spacing"]
    T, T2, Ci = (np.asarray(a, np.float64) for a in (T, T2, Ci))
    for _ in range(nsteps):
        new = T2.copy()
        c = T[1:-1, 1:-1, 1:-1]
        lap = ((T[2:, 1:-1, 1:-1] - 2 * c + T[:-2, 1:-1, 1:-1]) * ix ** 2
               + (T[1:-1, 2:, 1:-1] - 2 * c + T[1:-1, :-2, 1:-1]) * iy ** 2
               + (T[1:-1, 1:-1, 2:] - 2 * c + T[1:-1, 1:-1, :-2]) * iz ** 2)
        new[1:-1, 1:-1, 1:-1] = c + p["dt"] * p["lam"] * Ci[1:-1, 1:-1,
                                                            1:-1] * lap
        T, T2 = new, T
    return T, T2


def _np_gp(re, im, V, p, nsteps):
    re, im, V = (np.asarray(a, np.float64) for a in (re, im, V))
    dt, g, inv2 = p["dt"], p["g"], p["inv2"]

    def H(f, r, i, sl):
        c = f[sl]
        lap = 0.0
        for a in range(3):
            hi = tuple(slice(s.start + (a == b), s.stop + (a == b))
                       for b, s in enumerate(sl))
            lo = tuple(slice(s.start - (a == b), s.stop - (a == b))
                       for b, s in enumerate(sl))
            lap = lap + (f[hi] - 2 * c + f[lo]) * inv2[a]
        return -0.5 * lap + (V[sl] + g * (r[sl] ** 2 + i[sl] ** 2)) * c

    n = re.shape[0]
    s1 = (slice(1, n - 1),) * 3
    s2 = (slice(2, n - 2),) * 3
    for _ in range(nsteps):
        re1 = re.copy()
        re1[s1] = re[s1] + dt * H(im, re, im, s1)
        im_new = im.copy()
        im_new[s2] = im[s2] - dt * H(re1, re1, im, s2)
        re_new = re.copy()
        re_new[s2] = re1[s2]
        re, im = re_new, im_new
    return re, im


def test_fig1_reference_matches_numpy():
    cfg = _cfg("fig1-diffusion-512", 10)
    ref = harness.load_module("references", "diffusion3d")
    p = ref.params(cfg, 2 ** 35 + 3)
    s0 = ref.initial(cfg, p, tuple(cfg["grid"]))
    want = _np_fig1(s0["T"], s0["T2"], s0["Ci"], p, 7)
    got = compare.Reference(ref, cfg, p).replay(7)
    for g, w in zip((got["T"], got["T2"]), want):
        np.testing.assert_allclose(np.asarray(g), w, rtol=0, atol=2e-6)
    assert float(np.max(np.abs(np.asarray(got["T"]) - np.asarray(s0["T"])))) \
        > 1e-3                                   # the steps did something


def test_gp_reference_matches_numpy():
    cfg = _cfg("gp-512", 10)
    ref = harness.load_module("references", "gross_pitaevskii")
    p = ref.params(cfg, 77)
    s0 = ref.initial(cfg, p, tuple(cfg["grid"]))
    assert float(np.max(np.abs(np.asarray(s0["im"])))) > 1e-3
    want = _np_gp(s0["re"], s0["im"], s0["V"], p, 5)
    got = compare.Reference(ref, cfg, p).replay(5)
    for k, w in zip(("re", "im"), want):
        np.testing.assert_allclose(np.asarray(got[k]), w, rtol=0,
                                   atol=1e-6 * np.max(np.abs(w)))


@pytest.mark.parametrize("cell", list(harness.cells()))
def test_program_agrees_with_reference(cell):
    import time

    res = harness.run(cell, 2 ** 40 + 123, 0.2, False,
                      t_start=time.perf_counter(), allow_cpu=True,
                      rehearse_n=8)
    assert res["correct"], res["compared"]
    assert res["run"]["compiles_in_window"] == 0
    assert list(res)[-1] == "compared"


def test_seed_changes_data_not_work():
    cfg = harness.load_json("configs", "fig1-diffusion-512")
    ref = harness.load_module("references", "diffusion3d")
    a, b = ref.params(cfg, 1), ref.params(cfg, 2 ** 31 + 5)
    assert a["centre"] != b["centre"]
    assert a["dt"] == b["dt"] and a["spacing"] == b["spacing"]
