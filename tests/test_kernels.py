"""Per-kernel shape/dtype sweeps vs pure-jnp oracles (interpret mode)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import CoaddQuery, SpatialIndex, SurveyConfig, make_survey
from repro.core.mapper import query_grid_sky


# ------------------------------------------------------------------ warp ---
SURVEY = make_survey(SurveyConfig(n_runs=2, n_fields=3, n_sources=40,
                                  height=24, width=24))


@pytest.mark.parametrize("npix,block_rows", [(16, 8), (32, 8), (32, 16), (64, 8)])
def test_warp_kernel_matches_ref(npix, block_rows):
    from repro.kernels.warp import ops as wops
    from repro.kernels.warp import ref as wref
    q = CoaddQuery(band="r", ra_bounds=(37.1, 37.6), dec_bounds=(-0.5, 0.1), npix=npix)
    ids = SpatialIndex.build(SURVEY).select(q)[:6]
    assert len(ids) > 0
    gr, gd = map(jnp.asarray, query_grid_sky(q))
    px = jnp.asarray(np.stack([SURVEY.images[i].pixels for i in ids]))
    wv = jnp.asarray(np.stack([SURVEY.images[i].wcs.to_vector() for i in ids]))
    acc = jnp.ones((len(ids),), jnp.float32)
    t_r, c_r = wref.warp_batch_ref(px, wv, acc, gr, gd)
    assert float(jnp.abs(t_r).max()) > 0  # non-trivial
    # A one-image stack's coadd is that image's projected tile.
    for i in range(len(ids)):
        t_k, c_k = wops.coadd_fused(px[i:i + 1], wv[i:i + 1], acc[i:i + 1],
                                    gr, gd, block_rows=block_rows)
        np.testing.assert_allclose(np.asarray(t_k), np.asarray(t_r[i]),
                                   atol=2e-2, rtol=1e-4)
        np.testing.assert_array_equal(np.asarray(c_k), np.asarray(c_r[i]))


@pytest.mark.parametrize("npix", [32, 64])
def test_coadd_fused_kernel_matches_ref(npix):
    from repro.kernels.warp import ops as wops
    from repro.kernels.warp import ref as wref
    q = CoaddQuery(band="g", ra_bounds=(37.0, 37.7), dec_bounds=(-0.7, 0.3), npix=npix)
    ids = SpatialIndex.build(SURVEY).select(q)[:8]
    gr, gd = map(jnp.asarray, query_grid_sky(q))
    px = jnp.asarray(np.stack([SURVEY.images[i].pixels for i in ids]))
    wv = jnp.asarray(np.stack([SURVEY.images[i].wcs.to_vector() for i in ids]))
    acc = jnp.ones((len(ids),), jnp.float32)
    c_r, d_r = wref.coadd_fused_ref(px, wv, acc, gr, gd)
    c_k, d_k = wops.coadd_fused(px, wv, acc, gr, gd)
    np.testing.assert_allclose(np.asarray(c_k), np.asarray(c_r), atol=2e-2, rtol=1e-4)
    np.testing.assert_array_equal(np.asarray(d_k), np.asarray(d_r))


def test_warp_kernel_rejects_on_accept_gate():
    from repro.kernels.warp import ops as wops
    q = CoaddQuery(band="r", ra_bounds=(37.1, 37.6), dec_bounds=(-0.5, 0.1), npix=32)
    ids = SpatialIndex.build(SURVEY).select(q)[:2]
    gr, gd = map(jnp.asarray, query_grid_sky(q))
    px = jnp.asarray(np.stack([SURVEY.images[i].pixels for i in ids]))
    wv = jnp.asarray(np.stack([SURVEY.images[i].wcs.to_vector() for i in ids]))
    t, c = wops.coadd_fused(px, wv, jnp.zeros((2,), jnp.float32), gr, gd)
    assert float(jnp.abs(t).max()) == 0 and float(jnp.abs(c).max()) == 0


# ------------------------------------------------------------- attention ---
@pytest.mark.parametrize("hq,hkv,s,d,causal,window,dtype", [
    (4, 4, 128, 32, True, None, jnp.float32),
    (4, 2, 256, 64, True, None, jnp.float32),
    (8, 1, 128, 32, False, None, jnp.float32),
    (4, 2, 256, 64, True, 64, jnp.float32),
    (4, 2, 128, 64, True, None, jnp.bfloat16),
])
def test_flash_attention_sweep(hq, hkv, s, d, causal, window, dtype):
    from repro.kernels.attention import ops as aops
    from repro.kernels.attention.ref import mha_ref
    key = jax.random.PRNGKey(42)
    q = jax.random.normal(key, (2, hq, s, d), dtype)
    k = jax.random.normal(jax.random.fold_in(key, 1), (2, hkv, s, d), dtype)
    v = jax.random.normal(jax.random.fold_in(key, 2), (2, hkv, s, d), dtype)
    o_k = aops.flash_attention(q, k, v, causal, window, 64, 64, True)
    o_r = mha_ref(q, k, v, causal=causal, window=window)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(o_k, np.float32),
                               np.asarray(o_r, np.float32), atol=tol, rtol=tol)


def test_flash_attention_grads_match_ref():
    from repro.kernels.attention import ops as aops
    from repro.kernels.attention.ref import mha_ref
    key = jax.random.PRNGKey(0)
    q = jax.random.normal(key, (1, 4, 128, 32))
    k = jax.random.normal(jax.random.fold_in(key, 1), (1, 2, 128, 32))
    v = jax.random.normal(jax.random.fold_in(key, 2), (1, 2, 128, 32))
    g1 = jax.grad(lambda q, k, v: aops.flash_attention(q, k, v, True, None, 64, 64, True).sum(),
                  argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(lambda q, k, v: mha_ref(q, k, v, causal=True).sum(),
                  argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


# ------------------------------------------------------------------- ssd ---
@pytest.mark.parametrize("t,h,n,p,chunk", [
    (128, 2, 16, 16, 32),
    (256, 3, 32, 16, 64),
    (64, 1, 8, 32, 64),   # chunk > needed
    (192, 2, 16, 16, 64),
])
def test_ssd_kernel_sweep(t, h, n, p, chunk):
    from repro.kernels.ssd import ops as sops
    from repro.kernels.ssd.ref import ssd_batched_ref
    key = jax.random.PRNGKey(1)
    a = jax.nn.sigmoid(jax.random.normal(key, (2, t, h))) * 0.95 + 0.02
    B = jax.random.normal(jax.random.fold_in(key, 1), (2, t, n))
    C = jax.random.normal(jax.random.fold_in(key, 2), (2, t, n))
    x = jax.random.normal(jax.random.fold_in(key, 3), (2, t, h, p))
    y_r = ssd_batched_ref(a, B, C, x)
    y_k = sops.ssd(a, B, C, x, chunk=chunk)
    scale = float(jnp.abs(y_r).max())
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_r),
                               atol=2e-4 * max(scale, 1.0))


def test_empty_slot_projects_to_exact_zeros():
    """A padding slot (all-zero WCS, det 0) maps to NaN source coordinates;
    its samples must still be exact zeros, not NaN * 0 (eager ops here do
    not get XLA's multiply-by-mask-to-select rewrite, and the TPU doesn't
    either)."""
    from repro.core.mapper import bilinear_sample, project_one

    nan = jnp.full((4, 4), jnp.nan, jnp.float32)
    val, m = bilinear_sample(jnp.ones((8, 8), jnp.float32), nan, nan)
    assert np.all(np.asarray(val) == 0) and np.all(np.asarray(m) == 0)
    gr = jnp.full((4, 4), 37.6, jnp.float32)
    tile, cov = project_one(jnp.ones((8, 8), jnp.float32),
                            jnp.zeros((8,), jnp.float32), jnp.float32(0.0),
                            gr, jnp.zeros((4, 4), jnp.float32))
    assert np.all(np.asarray(tile) == 0) and np.all(np.asarray(cov) == 0)
