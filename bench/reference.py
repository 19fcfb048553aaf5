"""The plain reference coadd, its control, and the comparison behind ``correct``.

A query is a band and an RA/Dec box with an ``npix`` x ``npix`` output grid:
a TAN grid centred on the box, ``max(ra span, dec span) / npix`` degrees per
pixel, whose pixel centres are stated as float32 sky coordinates (the
program's query grid carries them in float32; the rounding is part of what
is asked).  For every frame of the band whose footprint meets the box, the
reference maps each output pixel centre into the frame through the frame's
TAN WCS in float64, samples it bilinearly where it falls inside the frame,
and adds the sample to ``coadd`` and 1 to ``depth``.

Nothing here imports the program.  The TAN projection is written out below.

The comparison (`compare`) reads two numbers over the output pixels whose
coverage is not ambiguous, against the float64 reference:

* ``coadd_max_rel``: the largest |coadd - ref| / max(|ref|, 1);
* ``coadd_rms_rel``: the root mean square of the same.

A pixel is ambiguous when a frame edge passes within `EDGE_EPS_PX` of one of
its samples: float32 and float64 coordinates may then disagree on whether
the sample lies inside the frame.  The reference alone decides which pixels
those are.  A missing, doubled or extra frame, a wrong gate or a lost scan
step moves ``coadd`` by one whole sample (the background is 10) and so fails
both numbers; a precision lost in the pixels or the stack fails them too
(`control`).
"""

from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

DEG2RAD = np.pi / 180.0
RAD2DEG = 180.0 / np.pi
# Float32 sky-to-pixel arithmetic at SDSS frame size is good to about 1e-4
# pixel; samples nearer than this to a frame edge are ambiguous.
EDGE_EPS_PX = 2e-3


# ----- TAN (gnomonic) projection, float64 ---------------------------------

def sky_to_pixel(ra, dec, wcs):
    """Sky (ra, dec) degrees -> pixel (x, y) through a WCS vector
    [crval_ra, crval_dec, crpix_x, crpix_y, cd11, cd12, cd21, cd22]."""
    ra0, dec0, x0, y0, c11, c12, c21, c22 = (float(v) for v in wcs)
    dra = (np.asarray(ra, np.float64) - ra0) * DEG2RAD
    d = np.asarray(dec, np.float64) * DEG2RAD
    d0 = dec0 * DEG2RAD
    sd, cdc, cra = np.sin(d), np.cos(d), np.cos(dra)
    cosc = np.sin(d0) * sd + np.cos(d0) * cdc * cra
    xi = cdc * np.sin(dra) / cosc * RAD2DEG
    eta = (np.cos(d0) * sd - np.sin(d0) * cdc * cra) / cosc * RAD2DEG
    det = c11 * c22 - c12 * c21
    return (c22 * xi - c12 * eta) / det + x0, (-c21 * xi + c11 * eta) / det + y0


def pixel_to_sky(x, y, wcs):
    """Pixel (x, y) -> sky (ra, dec) degrees through a WCS vector."""
    ra0, dec0, x0, y0, c11, c12, c21, c22 = (float(v) for v in wcs)
    dx = np.asarray(x, np.float64) - x0
    dy = np.asarray(y, np.float64) - y0
    xi = (c11 * dx + c12 * dy) * DEG2RAD
    eta = (c21 * dx + c22 * dy) * DEG2RAD
    d0 = dec0 * DEG2RAD
    rho = np.hypot(xi, eta)
    c = np.arctan(rho)
    safe = np.where(rho == 0, 1.0, rho)
    dec = np.arcsin(np.cos(c) * np.sin(d0) + eta * np.sin(c) * np.cos(d0) / safe)
    ra = ra0 * DEG2RAD + np.arctan2(
        xi * np.sin(c),
        safe * np.cos(d0) * np.cos(c) - eta * np.sin(d0) * np.sin(c))
    dec = np.where(rho == 0, d0, dec)
    ra = np.where(rho == 0, ra0 * DEG2RAD, ra)
    return ra * RAD2DEG, dec * RAD2DEG


def grid_sky(box, npix: int):
    """(ra, dec) of the output pixel centres, each (npix, npix), as float32
    values held in float64."""
    ra0, ra1, dec0, dec1 = box
    scale = max(ra1 - ra0, dec1 - dec0) / npix
    c = (npix - 1) / 2.0
    g = [0.5 * (ra0 + ra1), 0.5 * (dec0 + dec1), c, c, scale, 0.0, 0.0, scale]
    g = np.asarray(g, np.float32).astype(np.float64)
    xs, ys = np.meshgrid(np.arange(npix, dtype=np.float64),
                         np.arange(npix, dtype=np.float64))
    ra, dec = pixel_to_sky(xs, ys, g)
    return (ra.astype(np.float32).astype(np.float64),
            dec.astype(np.float32).astype(np.float64))


# ----- the reference and its control --------------------------------------

def _bf16(x):
    """Round to bfloat16 (nearest even) and back to float64."""
    import ml_dtypes

    return np.asarray(x, np.float32).astype(ml_dtypes.bfloat16).astype(np.float64)


def _sample(img, sx, sy, lowp: bool):
    h, w = img.shape
    x0, y0 = np.floor(sx), np.floor(sy)
    dx, dy = sx - x0, sy - y0
    inside = (sx >= 0) & (sx <= w - 1) & (sy >= 0) & (sy <= h - 1)
    xa = np.clip(x0.astype(np.int64), 0, w - 1)
    xb = np.clip(x0.astype(np.int64) + 1, 0, w - 1)
    ya = np.clip(y0.astype(np.int64), 0, h - 1)
    yb = np.clip(y0.astype(np.int64) + 1, 0, h - 1)
    taps = [img[ya, xa], img[ya, xb], img[yb, xa], img[yb, xb]]
    wts = [(1 - dx) * (1 - dy), dx * (1 - dy), (1 - dx) * dy, dx * dy]
    if lowp:
        v = 0.0
        for t, wt in zip(taps, wts):
            v = _bf16(v + _bf16(_bf16(t) * _bf16(wt)))
    else:
        v = sum(t * wt for t, wt in zip(taps, wts))
    near = lambda s, hi: (np.abs(s) < EDGE_EPS_PX) | (np.abs(s - hi) < EDGE_EPS_PX)
    in_band = ((sx > -EDGE_EPS_PX) & (sx < w - 1 + EDGE_EPS_PX)
               & (sy > -EDGE_EPS_PX) & (sy < h - 1 + EDGE_EPS_PX))
    ambiguous = in_band & (near(sx, w - 1) | near(sy, h - 1))
    return np.where(inside, v, 0.0), inside, ambiguous


def coadd(archive, band: int, box, npix: int, lowp: bool = False,
          threads: int | None = None):
    """Reference (coadd, depth, ambiguous) of one query over ``archive``.

    ``lowp=True`` is the control: the same sums with every pixel, weight,
    product and partial sum rounded to bfloat16 (coordinates stay float64,
    since bfloat16 coordinates would land degrees away).
    """
    gr, gd = grid_sky(box, npix)
    frames = archive.overlapping(band, box)
    workers = max(1, min(threads or min(16, os.cpu_count() or 4), len(frames)))
    parts = np.array_split(frames, workers)

    def one(idx):
        c = np.zeros(gr.shape)
        d = np.zeros(gr.shape)
        a = np.zeros(gr.shape, bool)
        for k in idx:
            sx, sy = sky_to_pixel(gr, gd, archive.wcs[k])
            v, inside, amb = _sample(archive.pixels[k], sx, sy, lowp)
            c = _bf16(c + v) if lowp else c + v
            d += inside
            a |= amb
        return c, d, a

    with ThreadPoolExecutor(max_workers=workers) as pool:
        outs = list(pool.map(one, parts))
    c = np.zeros(gr.shape)
    for part in outs:
        c = _bf16(c + part[0]) if lowp else c + part[0]
    d = sum(o[1] for o in outs)
    a = np.logical_or.reduce([o[2] for o in outs])
    return c, d, a


def compare(coadd_out, depth_out, ref) -> dict:
    """The compared numbers of one answer against its reference."""
    ref_c, ref_d, amb = ref
    c = np.asarray(coadd_out, np.float64)
    d = np.asarray(depth_out, np.float64)
    if c.shape != ref_c.shape or d.shape != ref_d.shape:
        return {"coadd_max_rel": float("inf"), "coadd_rms_rel": float("inf"),
                "depth_mismatch": -1, "ambiguous_share": float(amb.mean())}
    keep = ~amb
    err = np.abs(c - ref_c)[keep] / np.maximum(np.abs(ref_c[keep]), 1.0)
    if not np.isfinite(err).all():
        mx = rms = float("inf")
    else:
        mx, rms = float(err.max()), float(np.sqrt(np.mean(err ** 2)))
    return {"coadd_max_rel": mx, "coadd_rms_rel": rms,
            "depth_mismatch": int((d != ref_d)[keep].sum()),
            "ambiguous_share": float(amb.mean())}


COMPARED = ("coadd_max_rel", "coadd_rms_rel")


def worst(readings) -> dict:
    """The worst of each compared number over several answers."""
    return {k: max(r[k] for r in readings) for k in COMPARED}
