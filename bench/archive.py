"""Seeded synthetic Stripe 82 archive at SDSS frame shape.

The sky model follows the program's survey generator: a global point-source
catalog, rendered through each run's Gaussian seeing onto TAN frames of
runs x camcols x fields x bands, plus a flat background and Gaussian noise.
The benchmark owns this copy so that no change to the program moves the
data it is measured on.  It departs from that generator in five ways:

* frames render on a thread pool, one generator per frame, straight into
  one (N, H, W) float32 array;
* sources splat through one ``bincount`` per frame instead of a loop;
* the per-run pointing offsets and rotations are bounded (uniform, not
  Gaussian), so a box kept away from the nominal frame edges by
  `edge_margin_deg` crosses the same edges in every run;
* the pointings are the deployment's, drawn once from the configuration's
  ``pointing_seed`` and not from the run's seed, as a survey's astrometry
  is fixed: the seed draws the sky, the noise and the seeing, and a box
  placed alike in two seeds' archives reads the same frame geometry;
* every WCS parameter is rounded to float32, the precision the archive
  stores, so the program and the reference read the same frame geometry.

Frames are ordered run, camcol, field, band, as the program's generator
orders them.  Nothing here imports the program.
"""

from __future__ import annotations

import dataclasses
import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from bench.reference import pixel_to_sky, sky_to_pixel

BANDS = ("u", "g", "r", "i", "z")
# Half-width of a rendered source cutout, in PSF sigmas: the Gaussian beyond
# it is below exp(-50) of the source flux.
SPLAT_SIGMAS = 10.0


@dataclasses.dataclass
class Archive:
    """The frames and their metadata, as numpy arrays indexed by frame."""

    cfg: dict
    pixels: np.ndarray   # (N, H, W) float32
    wcs: np.ndarray      # (N, 8) float64 holding float32 values:
                         #   crval_ra, crval_dec, crpix_x, crpix_y, cd11..cd22
    run: np.ndarray      # (N,) int
    camcol: np.ndarray
    field: np.ndarray
    band: np.ndarray
    seeing: np.ndarray   # (N,) PSF sigma in pixels
    bounds: np.ndarray   # (N, 4) ra_min, ra_max, dec_min, dec_max (float64)

    def __len__(self) -> int:
        return len(self.run)

    @property
    def nbytes(self) -> int:
        return int(self.pixels.nbytes)

    def overlapping(self, band: int, box) -> np.ndarray:
        """Frame indices of ``band`` whose footprint box meets ``box``
        (ra0, ra1, dec0, dec1), edges included."""
        b = self.bounds
        m = ((self.band == band) & (b[:, 1] >= box[0]) & (b[:, 0] <= box[1])
             & (b[:, 3] >= box[2]) & (b[:, 2] <= box[3]))
        return np.nonzero(m)[0]


@dataclasses.dataclass(frozen=True)
class Geometry:
    """Where the archive's frames lie: the nominal tiling and its jitter."""

    scale_deg: float      # degrees per pixel
    ra_start: float
    field_deg: float
    dec_min: float
    camcol_deg: float
    n_fields: int
    n_camcols: int
    jitter_frac: float    # per-run pointing offset, a share of a frame
    rotation_deg: float   # per-run rotation bound
    height: int
    width: int

    @staticmethod
    def of(cfg: dict) -> "Geometry":
        scale = cfg["pixel_arcsec"] / 3600.0
        return Geometry(
            scale_deg=scale,
            ra_start=cfg["ra_start"],
            field_deg=cfg["width"] * scale,
            dec_min=cfg["dec_center"] - 0.5 * cfg["camcols"] * cfg["height"] * scale,
            camcol_deg=cfg["height"] * scale,
            n_fields=cfg["fields"],
            n_camcols=cfg["camcols"],
            jitter_frac=cfg["pointing_jitter_frac"],
            rotation_deg=cfg["rotation_deg"],
            height=cfg["height"],
            width=cfg["width"],
        )

    @property
    def ra_edges(self) -> np.ndarray:
        return self.ra_start + self.field_deg * np.arange(self.n_fields + 1)

    @property
    def dec_edges(self) -> np.ndarray:
        return self.dec_min + self.camcol_deg * np.arange(self.n_camcols + 1)

    def edge_margin_deg(self, cell_deg: float) -> float:
        """How far a jittered frame edge can lie from its nominal place
        along one axis, plus two pixels."""
        half_diag = 0.5 * np.hypot(self.height, self.width) * self.scale_deg
        turn = half_diag * np.sin(np.deg2rad(self.rotation_deg))
        return self.jitter_frac * cell_deg + turn + 2.0 * self.scale_deg

    def centre_ranges(self, box_deg: float, axis: str, crossing: bool):
        """[lo, hi] ranges of box centres along ``axis`` ("ra" or "dec")
        whose box crosses one nominal frame edge of that axis (``crossing``)
        or none, every edge kept a margin away from the box's sides."""
        edges = self.ra_edges if axis == "ra" else self.dec_edges
        cell = self.field_deg if axis == "ra" else self.camcol_deg
        m = self.edge_margin_deg(cell)
        half = 0.5 * box_deg
        if crossing:
            out = [(e - half + m, e + half - m) for e in edges[1:-1]]
        else:
            out = [(a + half + m, b - half - m) for a, b in zip(edges, edges[1:])]
        out = [(lo, hi) for lo, hi in out if hi > lo]
        if not out:
            raise ValueError(
                f"no box of {box_deg:.4f} deg {'crosses' if crossing else 'fits'} "
                f"the {axis} frame edges with a {m:.4f} deg margin")
        return out


def seed_words(seed: int, *tail: int) -> list:
    """A non-negative entropy list for numpy from any whole-number seed,
    with ``tail`` naming one stream of it."""
    return [seed % (1 << 64), 0 if seed >= 0 else 1, *tail]


def _frame_wcs(ra_c, dec_c, theta, scale, h, w) -> np.ndarray:
    c, s = np.cos(theta), np.sin(theta)
    cd = np.array([[c, -s], [s, c]]) @ np.diag([scale, scale])
    v = np.array([ra_c, dec_c, (w - 1) / 2.0, (h - 1) / 2.0,
                  cd[0, 0], cd[0, 1], cd[1, 0], cd[1, 1]])
    return v.astype(np.float32).astype(np.float64)


def _frame_bounds(wcs: np.ndarray, h: int, w: int):
    xs = np.array([0.0, w - 1.0, 0.0, w - 1.0])
    ys = np.array([0.0, 0.0, h - 1.0, h - 1.0])
    ra, dec = pixel_to_sky(xs, ys, wcs)
    return ra.min(), ra.max(), dec.min(), dec.max()


def _render(out, wcs, seeing, cat_ra, cat_dec, cat_flux, background,
            noise_sigma, rng):
    """Sources through a Gaussian PSF, plus background and noise, into
    ``out`` (H, W) float32."""
    h, w = out.shape
    sx, sy = sky_to_pixel(cat_ra, cat_dec, wcs)
    r = int(np.ceil(SPLAT_SIGMAS * seeing))
    keep = (sx > -r) & (sx < w - 1 + r) & (sy > -r) & (sy < h - 1 + r)
    sx, sy, f = sx[keep], sy[keep], cat_flux[keep]
    off = np.arange(-r, r + 1)
    xs = np.floor(sx)[:, None, None] + off[None, None, :]
    ys = np.floor(sy)[:, None, None] + off[None, :, None]
    d2 = (xs - sx[:, None, None]) ** 2 + (ys - sy[:, None, None]) ** 2
    val = f[:, None, None] * np.exp(-0.5 * d2 / seeing ** 2)
    inside = (xs >= 0) & (xs < w) & (ys >= 0) & (ys < h)
    flat = (ys * w + xs)[inside].astype(np.int64)
    light = np.bincount(flat, weights=val[inside], minlength=h * w)
    rng.standard_normal(out=out, dtype=np.float32)
    out *= np.float32(noise_sigma)
    out += (background + light.reshape(h, w)).astype(np.float32)


def frame_table(cfg: dict, seed: int):
    """Every frame's metadata, WCS and footprint, and the source catalog:
    (meta, wcs, seeing, bounds, catalog), without rendering a pixel."""
    geo = Geometry.of(cfg)
    h, w = cfg["height"], cfg["width"]
    n_runs, n_cam, n_fld, n_band = (cfg["runs"], cfg["camcols"],
                                    cfg["fields"], cfg["bands"])
    words = seed_words(seed)
    rng = np.random.default_rng(words + [1])
    ra_hi = geo.ra_start + n_fld * geo.field_deg
    dec_hi = geo.dec_min + n_cam * geo.camcol_deg
    n_src = cfg["n_sources"]
    cat_ra = rng.uniform(geo.ra_start, ra_hi, n_src)
    cat_dec = rng.uniform(geo.dec_min, dec_hi, n_src)
    base = rng.pareto(2.0, n_src) * cfg["source_flux_max"] / 10.0
    cat_flux = base[:, None] * rng.uniform(0.6, 1.4, size=(n_src, n_band))

    n = n_runs * n_cam * n_fld * n_band
    meta = {k: np.zeros(n, np.int32) for k in ("run", "camcol", "field", "band")}
    wcs = np.zeros((n, 8))
    seeing = np.zeros(n)
    bounds = np.zeros((n, 4))
    i = 0
    for run in range(n_runs):
        pr = np.random.default_rng(seed_words(cfg["pointing_seed"], 2, run))
        dec_jit = pr.uniform(-1, 1) * geo.jitter_frac * geo.camcol_deg
        ra_jit = pr.uniform(-1, 1) * geo.jitter_frac * geo.field_deg
        theta = np.deg2rad(pr.uniform(-1, 1) * geo.rotation_deg)
        rr = np.random.default_rng(words + [2, run])
        sigma = cfg["psf_sigma_px"] * rr.uniform(0.85, 1.35)
        for camcol in range(n_cam):
            dec_c = geo.dec_min + (camcol + 0.5) * geo.camcol_deg + dec_jit
            for field in range(n_fld):
                ra_c = geo.ra_start + (field + 0.5) * geo.field_deg + ra_jit
                v = _frame_wcs(ra_c, dec_c, theta, geo.scale_deg, h, w)
                bb = _frame_bounds(v, h, w)
                for band in range(n_band):
                    meta["run"][i], meta["camcol"][i] = run, camcol
                    meta["field"][i], meta["band"][i] = field, band
                    wcs[i], seeing[i], bounds[i] = v, sigma, bb
                    i += 1
    return meta, wcs, seeing, bounds, (cat_ra, cat_dec, cat_flux)


def make_archive(cfg: dict, seed: int, threads: int | None = None,
                 frames=None) -> Archive:
    """The archive of configuration ``cfg`` from ``seed``.  With ``frames``
    only those frames are rendered, each as the whole archive holds it, and
    ``pixels`` is a dict from frame index to its (H, W) array."""
    meta, wcs, seeing, bounds, (cat_ra, cat_dec, cat_flux) = frame_table(cfg, seed)
    words = seed_words(seed)
    shape = (cfg["height"], cfg["width"])
    if frames is None:
        pixels = np.empty((len(wcs), *shape), np.float32)
        frames = range(len(wcs))
    else:
        pixels = {int(k): np.empty(shape, np.float32) for k in frames}

    def render(k):
        _render(pixels[k], wcs[k], seeing[k], cat_ra, cat_dec,
                cat_flux[:, meta["band"][k]], cfg["background"],
                cfg["noise_sigma"], np.random.default_rng(words + [3, k]))

    workers = threads or min(16, os.cpu_count() or 4)
    with ThreadPoolExecutor(max_workers=workers) as pool:
        for fut in [pool.submit(render, int(k)) for k in frames]:
            fut.result()
    return Archive(cfg, pixels, wcs, meta["run"], meta["camcol"],
                   meta["field"], meta["band"], seeing, bounds)
