"""Golden parity for the robust reducers (ISSUE 10 satellite).

The reference is *plain numpy*, float32, mirroring `repro.core.reducer`
operation-for-operation, fed by per-image stacks the engine itself
produces: a single-image time-bounded query returns exactly the warped
tile + coverage that image contributes to any stack, so composing those
through the numpy reference gives the answer every robust path must
reproduce — eager, streaming (4x oversubscribed), brick-served, XLA and
Pallas, across all six access methods.

Depth comparisons are **bitwise**: depth is a sum of small coverage
weights, so any disagreement means a clip *decision* flipped, not a
rounding difference.  Coadd comparisons use the same 2e-3 tolerance the
existing cross-method mean-parity test needs — the engine accumulates
per-image contributions in pack-layout order, the reference in survey
order, and float32 summation order is the one thing the contract does
not pin.

Plus the two-pass contract itself: the fused single-dispatch composition
(`reducer.robust_local`) must be bitwise identical to running the
moments / histogram / clip passes as separate jitted programs with the
between-pass values as plain operands — that equivalence is what makes
the streaming multi-pass schedule legal.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    METHODS,
    CoaddEngine,
    CoaddQuery,
    SurveyConfig,
    make_survey,
)
from repro.core import reducer

ROBUST = ("clipped", "median")
CLIP_K = 3.0
NBINS = 16

QUERY = CoaddQuery(band="r", ra_bounds=(37.3, 37.9), dec_bounds=(-0.5, 0.3),
                   npix=32)


@pytest.fixture(scope="module")
def survey():
    return make_survey(SurveyConfig(n_runs=3, n_fields=4, n_sources=80,
                                    height=16, width=16))


@pytest.fixture(scope="module")
def engine(survey):
    return CoaddEngine(survey, pack_capacity=8)


@pytest.fixture(scope="module")
def per_image(engine):
    """(tiles, covs) — per-sample warped (npix, npix) slices via
    single-epoch time-bounded queries.  A ``t_obs`` selects one (run,
    field) strip whose camcol frames tile without overlap (depth <= 1
    everywhere), so each slice holds each pixel's contribution from at
    most ONE image — exactly the float32 samples the robust scans see;
    the numpy reference differs from the engine only in summation
    order."""
    tiles, covs = [], []
    times = sorted({float(im.t_obs) for im in engine.survey.images
                    if im.band == QUERY.band})
    for t in times:
        q = dataclasses.replace(QUERY, time_bounds=(t, t))
        r = engine.run(q, "sql_structured")
        if r.depth.max() > 0:
            assert r.depth.max() <= 1.0  # no overlap within one slice
            tiles.append(np.asarray(r.coadd, np.float32))
            covs.append(np.asarray(r.depth, np.float32))
    assert len(tiles) >= 3  # a stack, not a single image
    return np.stack(tiles), np.stack(covs)


def _np_robust(tiles, covs, reduce, clip_k=CLIP_K, nbins=NBINS):
    """Plain-numpy float32 mirror of reducer.robust_local."""
    f32 = np.float32
    t, c = tiles.astype(f32), covs.astype(f32)
    cov = c > 0
    x = np.where(cov, t / np.where(cov, c, f32(1.0)), f32(0.0)).astype(f32)
    s0, s1, s2 = c.sum(0), t.sum(0), (x * t).sum(0)
    pos = s0 > 0
    safe = np.where(pos, s0, f32(1.0))
    mu = np.where(pos, s1 / safe, f32(0.0))
    var = np.maximum(np.where(pos, s2 / safe, f32(0.0)) - mu * mu, f32(0.0))
    sigma = np.sqrt(var)
    if reduce == "median":
        lo = mu - sigma
        w = f32(2.0) * sigma / f32(nbins)
        inv_w = f32(1.0) / np.maximum(w, f32(1e-30))
        b = np.clip(np.floor((x - lo) * inv_w), 0, nbins - 1).astype(np.int32)
        hist = np.zeros((nbins,) + s0.shape, f32)
        for j in range(nbins):
            hist[j] = ((b == j) * np.where(cov, c, f32(0.0))).sum(0)
        csum = np.cumsum(hist, axis=0)
        j = np.argmax(csum >= f32(0.5) * s0, axis=0).astype(f32)
        center = lo + (j + f32(0.5)) * w
    else:
        center = mu
    thresh = f32(clip_k) * sigma + f32(1e-3) * np.abs(center) + f32(1e-12)
    # Division-free clip test, mirroring reducer.clip_local exactly.
    keep = cov & (np.abs(t - c * center) <= c * thresh)
    return (np.where(keep, t, f32(0.0)).sum(0),
            np.where(keep, c, f32(0.0)).sum(0))


@pytest.fixture(scope="module")
def golden(per_image):
    tiles, covs = per_image
    return {red: _np_robust(tiles, covs, red) for red in ROBUST}


# ----- every access method, XLA eager path -----

@pytest.mark.parametrize("method", METHODS)
@pytest.mark.parametrize("red", ROBUST)
def test_methods_match_golden(engine, golden, method, red):
    ref_c, ref_d = golden[red]
    r = engine.run(QUERY, method, reduce=red)
    assert r.stats.reduce == red
    np.testing.assert_array_equal(r.depth, ref_d)     # clip decisions
    np.testing.assert_allclose(r.coadd, ref_c, atol=2e-3)


# ----- streaming multi-pass at 4x oversubscription -----

@pytest.mark.parametrize("red", ROBUST)
def test_streaming_matches_golden(survey, golden, red):
    probe = CoaddEngine(survey, pack_capacity=8)
    ds = probe.exec_dataset("structured")[0]
    budget = max(ds.chunk_nbytes(0, ds.n_packs) // 4, 1)
    eng = CoaddEngine(survey, pack_capacity=8, device_budget_bytes=budget,
                      stream_chunk_packs=2)
    ref_c, ref_d = golden[red]
    r = eng.run(QUERY, "sql_structured", reduce=red)
    assert r.stats.windows > 1                         # actually streamed
    assert r.stats.reduce == red
    assert r.stats.reduce_passes == (3 if red == "median" else 2)
    np.testing.assert_array_equal(r.depth, ref_d)
    np.testing.assert_allclose(r.coadd, ref_c, atol=2e-3)


# ----- brick-served template path -----

@pytest.mark.parametrize("red", ROBUST)
def test_bricks_match_golden(engine, golden, red):
    ref_c, ref_d = golden[red]
    r = engine.run(QUERY, "sql_structured", use_bricks=True, reduce=red)
    assert r.stats.reduce == red
    np.testing.assert_array_equal(r.depth, ref_d)
    np.testing.assert_allclose(r.coadd, ref_c, atol=2e-3)


# ----- Pallas reduction kernels vs the XLA scan -----

@pytest.mark.parametrize("red", ROBUST)
def test_pallas_matches_xla(survey, engine, red):
    kern = CoaddEngine(survey, pack_capacity=8, use_kernel=True)
    a = engine.run(QUERY, "sql_structured", reduce=red)
    b = kern.run(QUERY, "sql_structured", reduce=red)
    np.testing.assert_array_equal(a.depth, b.depth)
    np.testing.assert_allclose(a.coadd, b.coadd, atol=1e-4)


# ----- run_batch carries the estimator through -----

def test_run_batch_matches_single(engine, golden):
    queries = [QUERY, dataclasses.replace(QUERY, npix=32, band="r")]
    for red in ROBUST:
        ref_c, ref_d = golden[red]
        rs = engine.run_batch(queries, "sql_structured", reduce=red)
        for r in rs:
            assert r.stats.reduce == red
            np.testing.assert_array_equal(r.depth, ref_d)
            np.testing.assert_allclose(r.coadd, ref_c, atol=2e-3)


# ----- mean stays mean -----

def test_mean_unchanged_by_robust_plumbing(engine, per_image):
    tiles, covs = per_image
    r = engine.run(QUERY, "sql_structured")
    assert r.stats.reduce == "mean"
    assert r.stats.reduce_passes == 1
    np.testing.assert_array_equal(r.depth, covs.sum(0))
    np.testing.assert_allclose(r.coadd, tiles.sum(0), atol=2e-3)


# ----- two-pass == single-pass, bitwise, on one in-memory stack -----

@pytest.mark.parametrize("red", ROBUST)
def test_two_pass_equals_fused(red):
    rng = np.random.default_rng(11)
    tiles = jnp.asarray(rng.uniform(2, 9, (14, 8, 8)).astype(np.float32))
    covs = jnp.asarray(
        (rng.uniform(size=(14, 8, 8)) < 0.85).astype(np.float32))
    tiles = tiles * covs

    fused_c, fused_d = jax.jit(
        lambda t, c: reducer.robust_local(t, c, red, CLIP_K, NBINS)
    )(tiles, covs)

    # The streaming schedule: each pass its own program, between-pass
    # values crossing as plain arrays.  Must be bitwise — this is the
    # equivalence that lets a kill land between passes.
    s0, s1, s2 = jax.jit(reducer.moments_local)(tiles, covs)
    if red == "median":
        lo, w, inv_w = jax.jit(
            lambda a, b, c: reducer.hist_bounds(a, b, c, NBINS)
        )(s0, s1, s2)
        hist = jax.jit(
            lambda t, c, lo, iw: reducer.hist_local(t, c, lo, iw, NBINS)
        )(tiles, covs, lo, inv_w)
        center = jax.jit(reducer.hist_median)(hist, s0, lo, w)
        _, sigma = jax.jit(reducer.clip_stats)(s0, s1, s2)
    else:
        center, sigma = jax.jit(reducer.clip_stats)(s0, s1, s2)
    thresh = jax.jit(
        lambda c, s: reducer.clip_threshold(c, s, CLIP_K)
    )(center, sigma)
    pass_c, pass_d = jax.jit(reducer.clip_local)(tiles, covs, center, thresh)

    np.testing.assert_array_equal(np.asarray(fused_c), np.asarray(pass_c))
    np.testing.assert_array_equal(np.asarray(fused_d), np.asarray(pass_d))
