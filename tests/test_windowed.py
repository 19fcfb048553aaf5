"""The windowed warp (`kernels.warp.windowed`): the kernel against the XLA
gather, in both resident layouts, its guard, its window-fit rule, its VMEM
estimate, and the engine's choice of it (`JobStats.windowed_packs`).

The kernel runs in the Pallas interpreter here.  Its sample coordinates and
the gather's come from two separately compiled programs and may differ by
one float32 ulp (3e-5 px at 384 px); on a sky of 1000 counts with noise 3
that moves a coadd by ~1e-7 of itself, while one wrong tap moves it by
~4e-4.  So the coadd is compared at 1e-6 and the depth bit for bit.
"""
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = Path(__file__).resolve().parents[1]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from bench import program_spans  # noqa: E402
from repro.core import CoaddEngine, CoaddQuery, SurveyConfig, make_survey  # noqa: E402
from repro.core import mapper, reducer  # noqa: E402
from repro.core.geometry import make_grid_wcs, pixel_to_sky  # noqa: E402
from repro.kernels import interpret_mode  # noqa: E402
from repro.kernels.warp import windowed  # noqa: E402

SCALE = 0.396 / 3600.0      # deg / px, native for frames and grid
Q = 64
CAP = 8
SHORT = 70                  # the frame axis that does not ride the lanes


def _grid(dec=0.0, q=Q, scale=SCALE):
    g = make_grid_wcs(37.0, dec, q, q * scale).to_vector().astype(np.float64)
    xs, ys = np.meshgrid(np.arange(q, dtype=np.float64),
                         np.arange(q, dtype=np.float64))
    ra, dec = pixel_to_sky(xs, ys, g)
    return g, jnp.asarray(ra, jnp.float32), jnp.asarray(dec, jnp.float32)


def _frame(h, w, dx_px, dy_px, rot_deg, dec=0.0):
    """WCS of an h x w frame whose centre sits (dx, dy) px from the grid's
    centre, rotated by ``rot_deg``."""
    th = np.deg2rad(rot_deg)
    c, s = np.cos(th), np.sin(th)
    return [37.0 + dx_px * SCALE / np.cos(np.deg2rad(dec)),
            dec + dy_px * SCALE, (w - 1) / 2, (h - 1) / 2,
            c * SCALE, -s * SCALE, s * SCALE, c * SCALE]


def _shape(lanes, long):
    """(h, w) of a frame whose ``lanes`` axis is ``long`` pixels, and the
    rotation that lays that axis along the grid's rows: frames stored with
    their long axis as rows ("y"), or as columns, turned 90 degrees ("x"),
    as published SDSS frames are."""
    return ((long, SHORT), 0.0) if lanes == "y" else ((SHORT, long), 90.0)


def _pack(h, w, placements, turn=0.0, dec=0.0, seed=0):
    """(2, CAP, h, w) pixels and pack 1's (CAP, 8) WCS from placements."""
    rng = np.random.default_rng(seed)
    pix = (1000.0 + rng.normal(0.0, 3.0, (2, CAP, h, w))).astype(np.float32)
    wcs = np.zeros((CAP, 8), np.float32)
    for i, p in enumerate(placements):
        if p is not None:
            dx, dy, rot = p
            wcs[i] = _frame(h, w, dx, dy, rot + turn, dec)
    return pix, wcs


def _gather(pix, wcs, accept, gra, gdec):
    tiles, covs = mapper.map_batch(jnp.asarray(pix[1]), jnp.asarray(wcs),
                                   jnp.asarray(accept), gra, gdec)
    return reducer.reduce_local(tiles, covs)


def _both(pix, wcs, accept, fit, dec=0.0):
    _, gra, gdec = _grid(dec)
    c_w, d_w, took = windowed.coadd_windowed(
        jnp.asarray(pix), jnp.int32(1), jnp.asarray(wcs), jnp.asarray(accept),
        gra, gdec, fit=fit, interpret=interpret_mode())
    c_g, d_g = _gather(pix, wcs, accept, gra, gdec)
    return [np.asarray(a) for a in (c_w, d_w, c_g, d_g)] + [int(took)]


def _fit(h, w, wcs, lanes, dec=0.0):
    frames = wcs[np.abs(wcs).sum(1) > 0]
    return windowed.window_fit(_grid(dec)[0], frames, Q, h, w, CAP, lanes)


def _close(c_w, d_w, c_g, d_g, rtol=1e-6):
    assert d_g.sum() > 0
    assert np.isfinite(c_w).all()
    np.testing.assert_array_equal(d_w, d_g)
    rel = np.abs(c_w - c_g) / np.maximum(np.abs(c_g), 1.0)
    assert rel.max() < rtol


# Frame centres (dx, dy px from the grid's centre, rotation deg): inside,
# across each edge and corner of the 64x64 box, off it, and an empty slot.
EDGES = [(0, 0, 0.4), (40, 0, -0.4), (0, -30, 0.2), (-45, 35, 0.3),
         (38, -200, -0.1), (500, 0, 0.0), None, (-2, 3, -0.3)]


# Up to the widest window's limit: 24 deg still fits WIN_M_BUCKETS[-1].
ROTATED = [(0, 0, 24.0), (20, -10, -24.0), (-15, 25, 23.5), (30, 30, -20.0),
           (-35, -40, 12.0), (5, -5, -6.0), (0, 60, 24.0), (-60, 0, 0.0)]


@pytest.mark.parametrize("lanes", ["y", "x"])
@pytest.mark.parametrize("long", [128, 384], ids=["whole", "windowed"])
@pytest.mark.parametrize("case", ["edges", "rotated", "gated"])
def test_windowed_matches_gather(lanes, long, case):
    placements = ROTATED if case == "rotated" else EDGES
    accept = np.ones(CAP, bool)
    if case == "gated":
        accept[[0, 3, 5]] = False
    (h, w), turn = _shape(lanes, long)
    pix, wcs = _pack(h, w, placements, turn, seed=long + len(case))
    fit = _fit(h, w, wcs, lanes)
    assert fit is not None and fit.lanes == lanes
    assert fit.win_m == (windowed.WIN_M_BUCKETS[-1] if case == "rotated"
                         else 24)
    *out, took = _both(pix, wcs, accept, fit)
    assert took == 1
    _close(*out)


@pytest.mark.parametrize("lanes", ["y", "x"])
def test_empty_and_rejected_slots_give_zero(lanes):
    """An all-zero WCS (padding slot) projects to NaN and must add 0, not
    NaN; accepted-off slots add nothing either."""
    placements = [None] * CAP
    placements[2] = (0, 0, 0.1)
    (h, w), turn = _shape(lanes, 384)
    pix, wcs = _pack(h, w, placements, turn)
    fit = _fit(h, w, wcs, lanes)
    accept = np.zeros(CAP, bool)
    c_w, d_w, _, _, took = _both(pix, wcs, accept, fit)
    assert took == 1 and not c_w.any() and not d_w.any()
    accept[[0, 1]] = True           # empty slots, accepted
    c_w, d_w, _, _, took = _both(pix, wcs, accept, fit)
    assert took == 1
    assert np.isfinite(c_w).all() and not c_w.any() and not d_w.any()


@pytest.mark.parametrize("lanes", ["y", "x"])
def test_guard_sees_a_window_overflow(lanes):
    """A window one bucket too narrow for the geometry (what a misjudged
    fit would dispatch) loses taps, and the guard says so: the pack step
    reads 0, which voids the answer (the engine then redoes it through the
    gather, `test_engine_redoes_a_voided_scan`)."""
    (h, w), turn = _shape(lanes, 384)
    pix, wcs = _pack(h, w, ROTATED, turn, seed=3)
    fit = _fit(h, w, wcs, lanes)._replace(win_m=windowed.WIN_M_BUCKETS[-2])
    c_w, _, c_g, _, took = _both(pix, wcs, np.ones(CAP, bool), fit)
    assert took == 0
    assert (np.abs(c_w - c_g) / np.maximum(np.abs(c_g), 1.0)).max() > 1e-2
    # Only the accepted slots count: the overflowing frames gated off, the
    # rest fit the narrow window and the kernel runs.
    accept = np.array([abs(p[2]) < 10 for p in ROTATED])
    *out, took = _both(pix, wcs, accept, fit)
    assert took == 1
    _close(*out)


def test_bucket_edge():
    """A rotation whose tile span lands just inside the 24-px bucket takes
    it and matches the gather; one just past it takes 32."""
    (h, w), turn = _shape("y", 384)

    def at(rot):
        pix, wcs = _pack(h, w, [(0, 0, rot), (10, -20, -rot)] + [None] * 6,
                         turn, seed=7)
        return pix, wcs, _fit(h, w, wcs, "y")

    # need = (15 cos r + 63 sin r) * 1.01 + 5 against 24: r ~ 3.5 deg.
    inside = next(r for r in np.arange(3.0, 4.5, 0.05) if at(r + 0.05)[2].win_m > 24)
    pix, wcs, fit = at(inside)
    assert fit.win_m == 24
    assert at(inside + 0.05)[2].win_m == 32
    accept = np.array([True, True] + [False] * 6)
    *out, took = _both(pix, wcs, accept, fit)
    assert took == 1
    _close(*out)


def test_high_declination():
    """At high declination a frame whose tangent point sits off the grid's
    in RA is turned by about dRA sin(dec) against it, which the CD matrices
    alone do not show: the fit rule sees the turn, and the kernel matches
    the gather there.

    At dec 70 one float32 ulp of sin(dec) (6e-8) moves a sample by 0.03 px,
    and the two programs round their trigonometry differently: the coadds
    agree to ~1e-4 here.  A tap lost outside its window would move one by
    at least the noise between neighbours, 3e-3: the test holds them to
    1e-3."""
    (h, w), turn = _shape("y", 2048)
    # A north-up frame 1000 px east of a dec-60 grid: turned 0.19 deg, so
    # a 64-row tile spans 15 + 63 sin(0.19 deg) = 15.21 px along x.
    frame = np.array([_frame(h, w, 1000, 0, 0.0, dec=60.0)])
    sx, sy = windowed._tile_spans(_grid(60.0)[0], frame, Q, 64, 16)
    assert 15.15 < sx < 15.3 and abs(sy - 63) < 0.05

    dec = 70.0
    (h, w), turn = _shape("y", 384)
    offsets = [(-40, 0, 0.2), (40, 10, -0.2), (30, -150, 0.0),
               (-35, 120, 0.1), (0, 0, 0.0), (20, 5, 0.3), None, (-30, 0, 0.0)]
    pix, wcs = _pack(h, w, offsets, turn, dec=dec, seed=11)
    fit = _fit(h, w, wcs, "y", dec)
    assert fit is not None
    *out, took = _both(pix, wcs, np.ones(CAP, bool), fit, dec)
    assert took == 1
    _close(*out, rtol=1e-3)


def _sdss(rots, lanes="y", grid_scale=1.0, npix=1024, cap=16, shape=None):
    """The fit for SDSS-sized frames: 2048 rows by 1489 columns, north up
    ("y"), or the published 1489 by 2048, turned 90 degrees ("x")."""
    turn = _shape(lanes, 2048)[1]
    h, w = shape or ((2048, 1489) if lanes == "y" else (1489, 2048))
    grid = make_grid_wcs(37.0, 0.0, npix, npix * SCALE * grid_scale)
    frames = np.array([_frame(h, w, 0, 0, r + turn) for r in rots])
    return windowed.window_fit(grid.to_vector(), frames, npix, h, w, cap, lanes)


def test_window_fit_rule():
    # The benchmark's geometry, in either orientation: native scale,
    # rotations within 0.4 deg; the tile's long side follows the lanes.
    assert _sdss([0.4, -0.4, 0.0]) == windowed.WindowFit(64, 16, 24, 256, "y")
    assert _sdss([0.4, -0.4, 0.0], "x") == windowed.WindowFit(64, 16, 24, 256, "x")
    turned = np.array([_frame(2048, 1489, 0, 0, 90.0)])
    assert windowed.window_fit(_grid(q=1024)[0], turned, 1024, 2048, 1489, 16,
                               "y") == windowed.WindowFit(16, 64, 24, 256, "y")
    assert _sdss([10.0]).win_m == 32
    assert _sdss([30.0]) is None                     # rotation over the limit
    assert _sdss([0.0], grid_scale=2.0) is None      # 2x coarser grid
    assert _sdss([0.0], npix=1000) is None           # grid not whole tiles
    assert _sdss([0.0], cap=12) is None              # slot axis not 8-aligned
    assert _sdss([0.0], cap=4) is None
    assert _sdss([0.0], "x", shape=(2048, 1489)) is None   # lanes not 128s
    g = _grid(q=1024)[0]
    assert windowed.window_fit(g, np.zeros((1, 8)), 1024, 2048, 1489, 16, "y") is None
    assert windowed.window_fit(g, np.zeros((0, 8)), 1024, 2048, 1489, 16, "y") is None
    assert windowed.window_fit(g, turned, 1024, 2048, 1489, 16, None) is None
    # The lane axis is whole 128-pixel tiles, read whole up to 256.
    for long, want in [(300, None), (96, None), (128, 128), (384, 256)]:
        fit = _sdss([0.0], shape=(long, 1489))
        assert (fit and fit.win_l) == want


def test_common_fit():
    a = windowed.WindowFit(64, 16, 24, 256, "y")
    assert windowed.common_fit([a, a._replace(win_m=32)]).win_m == 32
    assert windowed.common_fit([a, None]) is None
    assert windowed.common_fit([a, a._replace(lanes="x")]) is None
    assert windowed.common_fit([]) is None


def test_vmem_estimate_fits_the_scoped_limit():
    """Every fit the rule can choose stays inside the kernel's scoped VMEM
    limit, and the estimate grows with the window, as in
    `warp.autotune_block_rows`."""
    sizes = [windowed.windowed_vmem_bytes(
        windowed.WindowFit(*tile, wm, windowed.WIN_L, "y"))
        for tile in windowed.TILES for wm in windowed.WIN_M_BUCKETS]
    assert sizes[:4] == sorted(sizes[:4])
    assert max(sizes) <= windowed.VMEM_LIMIT_BYTES
    # The double-buffered 8-slot windows alone at 24x256, each with its
    # 8-slot tile row: 2 * 8 * 24 * 8 * 256 * 4 B.
    assert sizes[1] > 2 * 8 * 24 * 8 * 256 * 4
    too_wide = windowed.WindowFit(*windowed.TILES[0], 512, windowed.WIN_L, "y")
    assert windowed.windowed_vmem_bytes(too_wide) > windowed.VMEM_LIMIT_BYTES


def test_lane_axis_is_none_off_the_tpu():
    assert windowed.lane_axis(jnp.zeros((2, 8, 128, 96))) is None


# ----- the engine's choice ---------------------------------------------------

NATIVE = 0.0125             # deg / px of the survey's 128 x 96 frames


@pytest.fixture(scope="module")
def survey():
    # Wider than the widest window along x, so a coarse grid can overflow it.
    return make_survey(SurveyConfig(n_runs=4, n_fields=4, n_sources=100,
                                    height=128, width=96,
                                    field_ra_deg=96 * NATIVE,
                                    camcol_dec_deg=128 * NATIVE))


def _query(scale, dec0=-0.4):
    return CoaddQuery(band="r", ra_bounds=(37.5, 37.5 + 64 * scale),
                      dec_bounds=(dec0, dec0 + 64 * scale), npix=64)


def _rows_on_lanes(monkeypatch):
    """Stand in for a TPU's resident layout: frame rows on the lanes."""
    monkeypatch.setattr(windowed, "lane_axis", lambda px: "y")


@pytest.mark.parametrize("budget", [None, 2_000_000], ids=["resident", "streamed"])
def test_engine_counts_windowed_packs(survey, budget, monkeypatch):
    native, coarse = _query(NATIVE), _query(4 * NATIVE)
    eng = CoaddEngine(survey, pack_capacity=8, device_budget_bytes=budget)
    gather = {q: eng.run(q, "sql_structured") for q in (native, coarse)}
    assert all(r.stats.windowed_packs == 0 for r in gather.values())

    _rows_on_lanes(monkeypatch)
    win = eng.run(native, "sql_structured")
    assert win.stats.packs_scanned > 0
    assert win.stats.windowed_packs == win.stats.packs_scanned
    np.testing.assert_array_equal(win.depth, gather[native].depth)
    ref = gather[native].coadd
    assert (np.abs(win.coadd - ref) / np.maximum(np.abs(ref), 1.0)).max() < 1e-5
    # A 4x coarser grid reads 4x wider source spans: over the limit.
    over = eng.run(coarse, "sql_structured")
    assert over.stats.windowed_packs == 0
    np.testing.assert_array_equal(over.coadd, gather[coarse].coadd)
    # Robust stacks keep the gather.
    assert eng.run(native, "sql_structured", reduce="clipped").stats.windowed_packs == 0


@pytest.mark.parametrize("budget", [None, 2_000_000], ids=["resident", "streamed"])
def test_batched_answers_equal_solo_answers(survey, budget, monkeypatch):
    """A coalesced batch scans each query with the solo program, so its
    answers are the solo ones bit for bit; a batch whose queries do not all
    fit keeps the gather for all."""
    _rows_on_lanes(monkeypatch)
    eng = CoaddEngine(survey, pack_capacity=8, device_budget_bytes=budget)
    queries = [_query(NATIVE), _query(NATIVE, dec0=-0.1)]
    solo = [eng.run(q, "sql_structured") for q in queries]
    batch = eng.run_batch(queries, "sql_structured")
    for s, b in zip(solo, batch):
        assert s.stats.windowed_packs > 0
        assert b.stats.windowed_packs == s.stats.windowed_packs
        np.testing.assert_array_equal(b.coadd, s.coadd)
        np.testing.assert_array_equal(b.depth, s.depth)
    mixed = eng.run_batch([queries[0], _query(4 * NATIVE)], "sql_structured")
    assert [r.stats.windowed_packs for r in mixed] == [0, 0]


@pytest.mark.parametrize("budget", [None, 2_000_000], ids=["resident", "streamed"])
def test_engine_redoes_a_voided_scan(survey, budget, monkeypatch):
    """A fit too narrow for the plan (as a misjudging rule would give)
    must never reach an answer: the guard voids the windowed scan and the
    engine redoes the query, solo or batched, through the gather."""
    eng = CoaddEngine(survey, pack_capacity=8, device_budget_bytes=budget)
    queries = [_query(NATIVE), _query(NATIVE, dec0=-0.1)]
    gather = [eng.run(q, "sql_structured") for q in queries]
    _rows_on_lanes(monkeypatch)
    fit = windowed.window_fit
    monkeypatch.setattr(windowed, "window_fit", lambda *a: fit(*a)._replace(
        win_m=4))
    for r, g in zip([eng.run(q, "sql_structured") for q in queries]
                    + eng.run_batch(queries, "sql_structured"), gather * 2):
        assert r.stats.windowed_packs == 0
        np.testing.assert_array_equal(r.coadd, g.coadd)
        np.testing.assert_array_equal(r.depth, g.depth)


def test_windowed_packs_rides_on_the_dispatch_span(survey, monkeypatch, tmp_path):
    _rows_on_lanes(monkeypatch)
    eng = CoaddEngine(survey, pack_capacity=8)
    plan = eng.plan(_query(NATIVE), "sql_structured")
    eng.execute(plan)                       # compile outside the trace
    jax.profiler.start_trace(str(tmp_path))
    res = eng.execute(plan)
    jax.profiler.stop_trace()
    args = {s[0]: s[3] for s in program_spans.read(str(tmp_path))}
    assert args["coadd.execute.dispatch"]["windowed_packs"] == res.stats.windowed_packs > 0
