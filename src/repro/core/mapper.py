"""Map stage: per-image filter + projection onto the query grid.

Faithful to Algorithm 2: the mapper receives one image, checks bandpass and
bounds overlap, and — when accepted — projects ("Astrometry/interpolation")
the image onto the query's common coordinate system, emitting a projected
tile plus its coverage footprint.  Rejected images emit zeros, which is how
a masked SPMD program "discards" a false positive (paper Fig. 6): the
arithmetic cost of discarding is one multiply, matching the paper's
observation that mapper-side filtering is cheap (§4.1.4).

The projection is an *inverse* warp: for every output pixel we compute its
sky position once per query, on the device from the query's 12-float grid
vector (`grid_vector`, `query_grid`), then per image map sky -> source
pixel via the image's TAN WCS and bilinearly interpolate.  Inverse warping
avoids scatter — every output pixel is a gather, which is the TPU-friendly
formulation (scatters serialize; gathers vectorize) and the basis of the
Pallas kernel in `repro.kernels.warp`.

`bilinear_sample` reads its four taps as element-wise gathers out of the
whole image (or, in the scan, the whole pack): one scattered HBM word per
output pixel and tap.  On a TPU the engine's mean scan replaces it, where a
plan's geometry allows, with the windowed sampler
(`repro.kernels.warp.windowed`): per output tile of 64x16 pixels it copies
each frame's small source window (16-48 x 256 pixels, the 256 along the
frame axis on the lanes; VMEM ~5-10 MB a step) into VMEM once and selects
the taps there, taking the same coordinates, ``inside`` mask and NaN guard
as this module.  Its window-fit rule (`windowed.window_fit`) admits a plan
when every gated frame's footprint over a tile, projected in float64,
fits the window, and a guard on the device voids any pack step where a
tap would still fall outside (the engine then redoes the query here);
otherwise — large rotations, scale mismatches, the CPU backend, the robust
passes — this gather is the map stage.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import floatfloat as ff
from repro.core.geometry import DEG2RAD, RAD2DEG, sky_to_pixel
from repro.core.query import CoaddQuery


_D2R = ff.split_constant(DEG2RAD)
_R2D = ff.split_constant(RAD2DEG)


def grid_vector(wcs_vec: np.ndarray) -> np.ndarray:
    """`query_grid`'s operand, 12 float32 (48 bytes): a TAN grid's 8-float
    WCS vector, then sin and cos of its tangent point's dec, each computed
    on the host in float64 and split into a float-float pair."""
    g = np.asarray(wcs_vec, np.float32)
    d = np.float64(g[1]) * DEG2RAD
    trig = [x for f in (np.sin, np.cos) for x in ff.split_constant(f(d))]
    return np.concatenate([g, np.float32(trig)])


@functools.partial(jax.jit, static_argnums=1)
def query_grid(gvec: jnp.ndarray, npix: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Sky coordinates (ra, dec), each (npix, npix) float32 on the device,
    of the output grid whose `grid_vector` is ``gvec``: the float64 grid of
    `geometry.pixel_to_sky` rounded to float32, bit for bit.

    The inverse of `geometry.sky_to_tangent`, in a form whose only large
    operand is the tangent point: with (xi, eta) the pixel's tangent-plane
    offset in radians and A = cos(dec0) - eta sin(dec0), the point lies
    along (A, xi, sin(dec0) + eta cos(dec0)), so

        ra  = ra0  + atan(xi / A)                                 * RAD2DEG
        dec = dec0 + atan((eta - q sin(dec0)) / (1 + q cos(dec0))) * RAD2DEG

    where q = R - A = xi (xi / A) / (1 + R / A) and R = hypot(xi, A).  Each
    offset is carried in float-float (`floatfloat`, ~46 bits) and added to
    the tangent point in degrees with a single rounding, so the result is
    the float64 grid's rounding except where the exact value lies within
    ~1e-14 deg of a float32 rounding boundary.  Float32 arithmetic misses
    it by one ulp in ~0.05% of RA pixels (0.035 px at RA 37-40), which a
    star's wing turns into a 1% error.  The series tail is float32, so a
    grid degrees across is off by one ulp in a few pixels; pixels more than
    ``atan(floatfloat.ATAN_SERIES_MAX)`` (14 deg) from the tangent point,
    or beyond a pole, take float32 `atan2` (`tests/test_query_grid.py`).

    One small program per ``npix``, dispatched on its own: every executor
    scans the same float32 grid for a query, bit for bit.
    """
    g = gvec.astype(jnp.float32)
    ra0, dec0 = g[0], g[1]
    sin0, cos0 = (g[8], g[9]), (g[10], g[11])
    pix = jnp.arange(npix, dtype=jnp.float32)
    dx = ff.two_sum(pix[None, :], -g[2])
    dy = ff.two_sum(pix[:, None], -g[3])

    def rad(c1, c2):     # (c1 dx + c2 dy) * DEG2RAD
        return ff.mul(ff.add(ff.mul((c1, 0.0), dx), ff.mul((c2, 0.0), dy)), _D2R)

    xi, eta = rad(g[4], g[5]), rad(g[6], g[7])
    a = ff.add(cos0, ff.neg(ff.mul(eta, sin0)))
    u = ff.div(xi, a)
    w = u[0] * u[0] / (1.0 + jnp.sqrt(1.0 + u[0] * u[0]))   # R / A - 1
    q = ff.div(ff.mul(xi, u), ff.two_sum(ff.const(2.0), w))
    n = ff.add(eta, ff.neg(ff.mul(q, sin0)))
    m = ff.add((ff.const(1.0), ff.const(0.0)), ff.mul(q, cos0))
    v = ff.div(n, m)

    near = ((a[0] > 0) & (jnp.abs(u[0]) <= ff.ATAN_SERIES_MAX)
            & (jnp.abs(v[0]) <= ff.ATAN_SERIES_MAX))
    ra = jnp.where(near, ff.round_sum(ra0, ff.mul(ff.atan(u), _R2D)),
                   ra0 + jnp.arctan2(xi[0], a[0]) * np.float32(RAD2DEG))
    dec = jnp.where(near, ff.round_sum(dec0, ff.mul(ff.atan(v), _R2D)),
                    jnp.arctan2(sin0[0] + eta[0] * cos0[0], jnp.hypot(xi[0], a[0]))
                    * np.float32(RAD2DEG))
    return ra, dec


def query_grid_sky(query: CoaddQuery) -> Tuple[np.ndarray, np.ndarray]:
    """Sky coordinates (ra, dec), each (npix, npix) float32, of the output
    grid: `query_grid` of the query's WCS vector, computed on the device
    and copied back — the grid the engine scans, bit for bit."""
    ra, dec = query_grid(grid_vector(query.grid_wcs_vector()), query.npix)
    return np.asarray(ra), np.asarray(dec)


def bilinear_sample(image: jnp.ndarray, sx: jnp.ndarray, sy: jnp.ndarray):
    """Bilinear interpolation of `image` at float coords (sx, sy).

    Returns (values, inside_mask).  Out-of-bounds samples return 0 with
    mask 0 — the coverage map counts only true source pixels.
    """
    h, w = image.shape
    x0 = jnp.floor(sx)
    y0 = jnp.floor(sy)
    dx = sx - x0
    dy = sy - y0
    x0i = x0.astype(jnp.int32)
    y0i = y0.astype(jnp.int32)

    inside = (sx >= 0.0) & (sx <= w - 1.0) & (sy >= 0.0) & (sy <= h - 1.0)

    x0c = jnp.clip(x0i, 0, w - 1)
    x1c = jnp.clip(x0i + 1, 0, w - 1)
    y0c = jnp.clip(y0i, 0, h - 1)
    y1c = jnp.clip(y0i + 1, 0, h - 1)

    v00 = image[y0c, x0c]
    v01 = image[y0c, x1c]
    v10 = image[y1c, x0c]
    v11 = image[y1c, x1c]
    val = (
        v00 * (1 - dx) * (1 - dy)
        + v01 * dx * (1 - dy)
        + v10 * (1 - dx) * dy
        + v11 * dx * dy
    )
    # A select, not val * mask: an empty pack slot (all-zero WCS) projects
    # to NaN coordinates, and NaN * 0 is NaN on the TPU.
    return jnp.where(inside, val, 0.0), inside.astype(image.dtype)


def project_one(
    pixels: jnp.ndarray,       # (H, W)
    wcs_vec: jnp.ndarray,      # (8,)
    accept: jnp.ndarray,       # scalar bool/float: band+bounds+time+valid gate
    grid_ra: jnp.ndarray,      # (Q, Q)
    grid_dec: jnp.ndarray,     # (Q, Q)
):
    """Project one image onto the query grid. Returns (tile, coverage)."""
    sx, sy = sky_to_pixel(grid_ra, grid_dec, wcs_vec)
    val, cov = bilinear_sample(pixels, sx, sy)
    a = accept.astype(pixels.dtype)
    return val * a, cov * a


def acceptance_mask(
    band_id: jnp.ndarray,
    valid: jnp.ndarray,
    t_obs: jnp.ndarray,
    ra_min: jnp.ndarray,
    ra_max: jnp.ndarray,
    dec_min: jnp.ndarray,
    dec_max: jnp.ndarray,
    query: CoaddQuery,
) -> jnp.ndarray:
    """Vectorized Algorithm-2 acceptance test over a batch of images."""
    ra0, ra1 = query.ra_bounds
    dec0, dec1 = query.dec_bounds
    t0, t1 = query.time_window()
    ok = (
        (band_id == query.band_id)
        & valid
        & (ra_max >= ra0)
        & (ra_min <= ra1)
        & (dec_max >= dec0)
        & (dec_min <= dec1)
        & (t_obs >= t0)
        & (t_obs <= t1)
    )
    return ok


def gather_packs(
    pack_idx: jnp.ndarray,   # scalar (or (G,)) int32 pack index/indices
    pixels: jnp.ndarray,     # (P, cap, H, W) resident
    wcs_vecs: jnp.ndarray,   # (P, cap, 8)
    ints: dict,              # (P, cap) int32 columns
    floats: dict,            # (P, cap) float32 columns
    psf_kernels: jnp.ndarray | None = None,  # (P, cap, K) / (P, cap, K, K)
):
    """Gather gated pack(s) out of the resident arrays along the pack axis.

    The device half of sparse execution (DESIGN.md §5): the planner derives
    which packs a gate opens (`plan.sparse_pack_index`), and this `jnp.take`
    pulls them from the resident (P, cap, ...) arrays *inside* the jitted
    program — the scan then visits G packs instead of P, so map cost scales
    with selectivity while the dispatch count stays 1.  The engine calls it
    per scan step with a scalar traced index (a dynamic slice of one pack),
    which streams the gather through the scan instead of materializing a
    (G, cap, ...) compacted copy next to the resident layout.  Padding
    entries duplicate pack 0; the compacted gate masks their slots False,
    so they contribute exact zeros like any masked discard.
    """
    take = lambda a: jnp.take(a, pack_idx, axis=0)  # noqa: E731
    return (
        take(pixels),
        take(wcs_vecs),
        {k: take(v) for k, v in ints.items()},
        {k: take(v) for k, v in floats.items()},
        None if psf_kernels is None else take(psf_kernels),
    )


def map_batch(
    pixels: jnp.ndarray,     # (N, H, W)
    wcs_vecs: jnp.ndarray,   # (N, 8)
    accept: jnp.ndarray,     # (N,)
    grid_ra: jnp.ndarray,
    grid_dec: jnp.ndarray,
    psf_kernels: jnp.ndarray | None = None,  # (N, K) separable rows or
                                             # (N, K, K) measured-PSF taps
):
    """vmapped map stage over a batch of images -> (tiles, coverages).

    When ``psf_kernels`` is given, each image is first convolved to the
    engine's common target PSF — the PSF-matching step the paper deferred,
    inserted before warping so the projected tiles all share one
    point-spread function.  `psf.convolve_batch` dispatches on bank rank:
    separable (N, K) Gaussian rows, or full (N, K, K) measured-PSF
    homogenization taps (DESIGN.md §7).  The engine's matched-pixel cache
    usually pre-applies this on the XLA path (then ``psf_kernels`` arrives
    as None here); this in-dispatch hook remains the uncached baseline and
    the distributed/mesh path.
    """
    if psf_kernels is not None:
        from repro.core import psf

        pixels = psf.convolve_batch(pixels, psf_kernels)
    return jax.vmap(project_one, in_axes=(0, 0, 0, None, None))(
        pixels, wcs_vecs, accept, grid_ra, grid_dec
    )
