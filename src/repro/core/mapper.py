"""Map stage: per-image filter + projection onto the query grid.

Faithful to Algorithm 2: the mapper receives one image, checks bandpass and
bounds overlap, and — when accepted — projects ("Astrometry/interpolation")
the image onto the query's common coordinate system, emitting a projected
tile plus its coverage footprint.  Rejected images emit zeros, which is how
a masked SPMD program "discards" a false positive (paper Fig. 6): the
arithmetic cost of discarding is one multiply, matching the paper's
observation that mapper-side filtering is cheap (§4.1.4).

The projection is an *inverse* warp: for every output pixel we compute its
sky position once per query, then per image map sky -> source pixel via the
image's TAN WCS and bilinearly interpolate.  Inverse warping avoids
scatter — every output pixel is a gather, which is the TPU-friendly
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

from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.geometry import pixel_to_sky, sky_to_pixel
from repro.core.query import CoaddQuery


def query_grid_sky(query: CoaddQuery) -> Tuple[np.ndarray, np.ndarray]:
    """Sky coordinates (ra, dec), each (npix, npix), of the output grid.

    Depends only on the query — computed once per job on the host.
    """
    n = query.npix
    g = query.grid_wcs_vector().astype(np.float64)
    xs, ys = np.meshgrid(np.arange(n, dtype=np.float64), np.arange(n, dtype=np.float64))
    ra, dec = pixel_to_sky(xs, ys, g)
    return ra.astype(np.float32), dec.astype(np.float32)


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
