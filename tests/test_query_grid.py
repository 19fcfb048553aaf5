"""The query's output grid, built on the device from its WCS vector
(`mapper.query_grid`): its precision against the float64 TAN grid, that
every executor scans that one grid bit for bit, what the
``coadd.execute.grid`` span reports, and that brick-lattice plans keep
their float64 host lattice.

The grid is computed in float-float (`repro.core.floatfloat`) and rounded
once, so at the scales a survey query takes it is the float64 grid rounded
to float32 in every pixel: the benchmark's reference states the grid that
way, and one ulp of RA at 37-40 deg (0.035 px) moves a bright star's wing
by ~1%.  Grids degrees across carry the series tail in float32 and may be
one ulp off in a few pixels; beyond ~14 deg from the tangent point, or over
a pole, the grid takes float32 ``atan2``.
"""
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

REPO = Path(__file__).resolve().parents[1]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from bench import program_spans  # noqa: E402
from repro.core import CoaddEngine, CoaddQuery, SurveyConfig, make_survey  # noqa: E402
from repro.core import engine as engine_mod  # noqa: E402
from repro.core import floatfloat as ff  # noqa: E402
from repro.core import mapper  # noqa: E402
from repro.core.geometry import make_grid_wcs, pixel_to_sky  # noqa: E402

SCALE = 0.396 / 3600.0      # deg / px, SDSS native


def _grids(ra0, dec0, npix, scale=SCALE):
    """(device grid, float64 grid rounded to float32)."""
    g = make_grid_wcs(ra0, dec0, npix, npix * scale).to_vector()
    dev = [np.asarray(a) for a in mapper.query_grid(mapper.grid_vector(g), npix)]
    xs, ys = np.meshgrid(np.arange(npix, dtype=np.float64),
                         np.arange(npix, dtype=np.float64))
    ref = [a.astype(np.float32) for a in pixel_to_sky(xs, ys, g.astype(np.float64))]
    return dev, ref


def _ulps(got, ref):
    return np.abs(got.astype(np.float64) - ref) / np.spacing(np.abs(ref))


# Stripe 82: RA 37-40, dec -1.25..1.25; 1024^2 boxes at native scale (the
# benchmark's), small ones, and boxes on the equator and at dec 60 and 70.
EXACT = [(37.3, -1.2, 1024), (38.2, -0.7, 1024), (39.9, 1.2, 1024),
         (38.0, 0.5, 1024), (37.6, 0.9, 48), (39.1, -0.3, 48),
         (38.5, 0.0, 1024), (38.5, 0.03, 1024), (37.0, 60.0, 1024),
         (37.0, 70.0, 1024)]


@pytest.mark.parametrize("ra0,dec0,npix", EXACT)
def test_grid_is_the_float64_grid_rounded(ra0, dec0, npix):
    (ra, dec), (ra32, dec32) = _grids(ra0, dec0, npix)
    assert ra.shape == dec.shape == (npix, npix)
    assert ra.dtype == dec.dtype == np.float32
    np.testing.assert_array_equal(ra, ra32)
    np.testing.assert_array_equal(dec, dec32)


@pytest.mark.parametrize("ra0,dec0,npix,scale", [
    (37.5, 0.3, 128, 0.05),     # 6.4 deg, as the test surveys' queries are
    (150.0, -60.0, 512, 0.01),  # 5.1 deg
])
def test_grid_of_a_wide_query(ra0, dec0, npix, scale):
    """The series tail is float32: one ulp, in under 1% of pixels."""
    (ra, dec), (ra32, dec32) = _grids(ra0, dec0, npix, scale)
    for got, ref in ((ra, ra32), (dec, dec32)):
        assert _ulps(got, ref).max() <= 1
        assert (got != ref).mean() < 0.01


@pytest.mark.parametrize("ra0,dec0,npix,scale", [
    (10.0, 30.0, 64, 0.5),      # 32 deg: the corners lie past the series
    (200.0, 85.0, 64, 0.5),     # across the pole
])
def test_grid_beyond_the_series(ra0, dec0, npix, scale):
    """Pixels past ``ATAN_SERIES_MAX`` or a pole take float32 atan2: within
    one ulp of the coordinate's range (360 deg of RA, 90 of dec)."""
    (ra, dec), (ra32, dec32) = _grids(ra0, dec0, npix, scale)
    assert np.abs(ra.astype(np.float64) - ra32).max() <= np.spacing(np.float32(360))
    assert np.abs(dec.astype(np.float64) - dec32).max() <= np.spacing(np.float32(90))


# ----- float-float arithmetic under the compiler -------------------------------

def test_two_prod_keeps_46_bits_under_jit():
    rng = np.random.default_rng(7)
    a, b = (rng.uniform(-40, 40, 4096).astype(np.float32) for _ in range(2))
    p, e = jax.jit(ff.two_prod)(a, b)
    exact = a.astype(np.float64) * b.astype(np.float64)
    got = np.asarray(p, np.float64) + np.asarray(e, np.float64)
    assert (np.abs(got - exact) <= 2.0**-46 * np.abs(exact)).all()


def test_a_constant_in_a_sum_is_not_folded():
    """XLA folds ``(x + c) - c`` to ``x`` for a literal ``c``; `ff.const`
    keeps the error term of ``1 + x``."""
    x = np.float32([-0.0122173, 3e-4, 1e-9])
    s, e = jax.jit(lambda x: ff.two_sum(ff.const(1.0), x))(x)
    np.testing.assert_array_equal(np.asarray(s, np.float64) + np.asarray(e, np.float64),
                                  1.0 + x.astype(np.float64))


@pytest.mark.parametrize("dec0", [-89.5, -1.25, 0.0, 0.7, 37.0, 70.0, 90.0])
def test_grid_vector_carries_the_float64_trig(dec0):
    """`grid_vector` is the 8-float WCS vector, then sin and cos of its
    dec0 (the float32 value) as float-float pairs, to float64's precision."""
    g = make_grid_wcs(38.0, dec0, 64, 0.01).to_vector()
    v = mapper.grid_vector(g)
    assert v.dtype == np.float32 and v.shape == (12,)
    np.testing.assert_array_equal(v[:8], g)
    rad = np.float64(g[1]) * np.pi / 180
    pairs = v[8:].astype(np.float64)
    assert abs(pairs[0] + pairs[1] - np.sin(rad)) < 1e-15
    assert abs(pairs[2] + pairs[3] - np.cos(rad)) < 1e-15


# ----- the engine scans that grid --------------------------------------------

QUERY = CoaddQuery(band="r", ra_bounds=(37.2, 37.8), dec_bounds=(-0.5, 0.3),
                   npix=32)


@pytest.fixture(scope="module")
def survey():
    return make_survey(SurveyConfig(n_runs=2, n_fields=4, n_sources=60,
                                    height=16, width=16))


def _recording(monkeypatch):
    """Record the (grid_ra, grid_dec) each executor takes for its scan."""
    seen = []
    orig = CoaddEngine._plan_grids

    def recorded(self, plan):
        out = orig(self, plan)
        seen.append((np.asarray(out[0]), np.asarray(out[1])))
        return out
    monkeypatch.setattr(CoaddEngine, "_plan_grids", recorded)
    return seen


def test_execute_scans_query_grid_sky(survey):
    eng = CoaddEngine(survey, pack_capacity=8)
    plan = eng.plan(QUERY, "sql_structured")
    fn, args, *_ = eng._eager_program(plan)
    ra, dec = mapper.query_grid_sky(QUERY)
    assert fn is engine_mod._coadd_scan_sparse
    assert isinstance(args[-2], jax.Array)
    np.testing.assert_array_equal(np.asarray(args[-2]), ra)
    np.testing.assert_array_equal(np.asarray(args[-1]), dec)


def test_eager_streaming_and_batched_answers_agree(survey, monkeypatch):
    """Every executor scans the same float32 grid for a query, and the
    eager, batched and streamed (two windows) answers are equal bit for
    bit."""
    seen = _recording(monkeypatch)
    eager = CoaddEngine(survey, pack_capacity=8)
    ds = eager.exec_dataset("structured")[0]
    stream = CoaddEngine(survey, pack_capacity=8,
                         device_budget_bytes=ds.chunk_nbytes(0, ds.n_packs) // 4)
    re = eager.run(QUERY, "sql_structured")
    rb = eager.run_batch([QUERY, QUERY], "sql_structured")
    rs = stream.run(QUERY, "sql_structured")
    ra, dec = mapper.query_grid_sky(QUERY)
    assert len(seen) == 4
    for gr, gd in seen:
        np.testing.assert_array_equal(gr, ra)
        np.testing.assert_array_equal(gd, dec)
    assert re.depth.max() > 0
    assert rs.stats.windows > 1
    for r in rb + [rs]:
        np.testing.assert_array_equal(r.coadd, re.coadd)
        np.testing.assert_array_equal(r.depth, re.depth)


def _grid_span_args(eng, plan, tmp_path):
    eng.execute(plan)                       # compile outside the trace
    jax.profiler.start_trace(str(tmp_path))
    res = eng.execute(plan)
    jax.profiler.stop_trace()
    args = {s[0]: s[3] for s in program_spans.read(str(tmp_path))}
    return args["coadd.execute.grid"], res


def test_grid_span_on_device(survey, tmp_path):
    eng = CoaddEngine(survey, pack_capacity=8)
    args, res = _grid_span_args(eng, eng.plan(QUERY, "sql_structured"),
                                tmp_path)
    assert args["on_device"] == 1
    assert args["h2d_bytes"] == 12 * 4
    assert res.stats.h2d_bytes < 2 * 4 * QUERY.npix**2


BRICKS = dict(pack_capacity=8, brick_deg=0.5, brick_npix=16)


def test_grid_span_uploads_a_brick_lattice(survey, tmp_path):
    """A brick-lattice plan keeps its float64 host lattice and uploads it."""
    eng = CoaddEngine(survey, **BRICKS)
    wq = eng.brick_grid.window_query(1, 3, 0, 2, "r")
    plan = eng.plan(wq, "sql_structured")
    cover = eng.brick_grid.decompose(wq)
    plan.grid_sky = eng.brick_grid.window_sky(cover.r0, cover.r1,
                                              cover.c0, cover.c1)
    args, res = _grid_span_args(eng, plan, tmp_path)
    assert args["on_device"] == 0
    assert args["h2d_bytes"] == 2 * 4 * wq.npix**2
    np.testing.assert_array_equal(res.coadd,
                                  eng.run_window(wq, "sql_structured").coadd)


def test_brick_mosaic_still_equals_run_window(survey):
    eng = CoaddEngine(survey, **BRICKS)
    wq = eng.brick_grid.window_query(1, 3, 0, 2, "r")
    fresh = eng.run_window(wq, "sql_structured")
    served = eng.run(wq, "sql_structured", use_bricks=True)
    assert served.stats.bricks_missed == 4 and fresh.depth.max() > 0
    np.testing.assert_array_equal(served.coadd, fresh.coadd)
    np.testing.assert_array_equal(served.depth, fresh.depth)
