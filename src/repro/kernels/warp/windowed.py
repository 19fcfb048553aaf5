"""Windowed bilinear warp + reduce for one resident pack (DESIGN.md §2).

The XLA mapper (`mapper.bilinear_sample`) reads each of its four bilinear
taps as a scalar gather out of the whole pack: one scattered HBM word per
output pixel, slot and tap.  At native scale and small rotation, though, a
small block of output pixels reads a small block of each frame.  This kernel
tiles the (Q, Q) output grid and, per (tile, slot), copies that frame's
source window HBM -> VMEM in one DMA, then selects the taps densely on
chip:

  1. Per pixel, the source coordinates (sx, sy) come from the same TAN
     projection as the other kernels (`warp._sky_to_pixel`).
  2. Lane axis: a (win_l, n) matrix holding the two bilinear weights of
     every output pixel along the frame axis that rides the lanes,
     contracted with the window on the MXU at ``Precision.HIGHEST`` ->
     (win_m, n): each pixel's value interpolated along that axis, at every
     window position of the other ("major") axis.
  3. Major axis: the two weights along it as a (win_m, n) matrix,
     multiplied in and summed over the window on the VPU.
  4. The ``inside`` mask, select-not-multiply NaN guard and acceptance gate
     of `mapper.project_one`, accumulated over the pack's slots in the
     output block, so no (cap, Q, Q) stack of tiles, coordinates or
     coverage ever reaches HBM.

Layout (`lane_axis`).  On a TPU, XLA lays a resident (P, cap, H, W) float32
array of frames out with no padding where it can: one frame axis that is
whole 128-lane tiles minor, the slot axis second-minor.  Frames of 1489
rows by 2048 columns (the published SDSS orientation) get major-to-minor
(P, H, cap, W): the columns (x) ride the lanes.  Frames stored 2048 rows by
1489 columns get (P, W, cap, H): the rows (y) do.  The kernel reads either
through the transpose that makes it row-major, a bitcast, so a window is a
box of the major frame axis (any origin) by the lane axis (origin aligned
down to 128 lanes).  Any other layout keeps the gather: the view would be a
copy of the archive.

Tiles.  An output tile is 64 x 16 pixels (`TILES`), its long side along
whichever grid axis maps onto the frame's lane axis, where the window is
256 wide; n = 1024 output pixels ride the lanes of (1, n) rows.

Window-fit rule (`window_fit`, on the host, float64, once per plan): the
corners of nine sample tiles (the grid's corner, edge and centre tiles)
are projected into every gated frame through the full TAN chain, so the
rotation between the grid's and a frame's tangent points counts as well as
CD_frame^-1 CD_grid.  The largest span along each frame axis, plus 1% and
5 pixels (the bilinear neighbour and a pixel of slack each side), must fit
a major-axis window of ``WIN_M_BUCKETS`` (the smallest that fits is taken)
and, with the 127 pixels a 128-aligned origin may lose, ``WIN_L`` lanes.
Otherwise (large rotation or scale mismatch) the engine keeps the gather.

Guard (`_window_origins`, on the device, per pack step).  The origins come
from each tile's four corners projected by the kernel's own float32
projection: floored minimum less one pixel, clamped into the frame, the
lane origin aligned down to 128.  The same corners check that every
accepted slot's window holds its taps with a pixel to spare (floored
maximum plus two).  `coadd_windowed` returns that check with the pack's
sums: a pack step where it fails -- a geometry the sampled rule misjudged
-- has lost taps, and the engine voids the answer and redoes the query
through the XLA gather.  (A `lax.cond` onto the gather inside the program
would make XLA relayout the whole resident archive for the branch.)

A grid step takes one output tile and 8 slots.  The HBM slice of the slot
axis must be a whole (8, 128) tile row, so each slot's window DMA carries
its 7 neighbours' rows too (unread): 8x the bytes, 196 KB a slot at 24x256,
which still streams at ~4 ms a 16-slot pack step on a v5e.

VMEM per grid step (`windowed_vmem_bytes`): the double-buffered windows of
8 slots, each with its 8-slot tile row (2 * 8 * win_m * 8 * win_l); the
lane-weight matrix and its iota (2 * win_l * n); the interpolated values,
major-axis weights and their iota (3 * win_m * n); and the per-pixel rows
and output blocks, each (1, n) padded to 8 sublanes (~16 rows of 8 * n) --
all float32.  `window_fit` refuses a window over ``VMEM_LIMIT_BYTES``.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import geometry
from repro.kernels.warp.warp import (
    LANES, _HIGHEST, _SMEM, _image_records, _sky_to_pixel,
)

#: Output tiles (rows, cols) the fit rule may choose: 1024 pixels, the long
#: side along the grid axis that maps onto the frame's lane axis.
TILES = ((64, 16), (16, 64))
#: Window widths along the frame's major axis the fit rule may choose,
#: smallest first; each is a separate compiled program, so the set stays
#: small.
WIN_M_BUCKETS = (16, 24, 32, 48)
#: Window width along the frame's lane axis: two 128-lane tiles.
WIN_L = 256
#: Per-step VMEM the windowed kernel may use (the scoped default on v5e).
VMEM_LIMIT_BYTES = 16 << 20
#: Slots a window DMA carries: one (8, 128) tile row of the slot axis.
SLOT_GROUP = 8
#: The transpose of (P, cap, H, W) that is row-major in the layout the
#: kernel reads, by the frame axis on the lanes: a bitcast, never a copy.
_VIEWS = {"y": (0, 3, 1, 2), "x": (0, 2, 1, 3)}


class WindowFit(NamedTuple):
    """Static shape of one windowed scan: output tile and source window."""
    tile_rows: int
    tile_cols: int
    win_m: int      # along the frame's major axis
    win_l: int      # along the frame's lane axis
    lanes: str      # "x" or "y": the frame axis on the lanes


def lane_axis(pixels) -> Optional[str]:
    """The frame axis ("x" or "y") on the lanes of resident (P, cap, H, W)
    pixels the kernel can read, or None: off a TPU (the CPU backend runs
    the XLA gather), or in a layout whose transposed view would be a copy.
    The kernel's DMAs rely on this: the layout is read, not assumed."""
    if jax.default_backend() != "tpu":
        return None
    m2m = tuple(pixels.format.layout.major_to_minor)
    return next((ax for ax, v in _VIEWS.items() if v == m2m), None)


def windowed_vmem_bytes(fit: WindowFit) -> int:
    """Per-grid-step VMEM estimate of `coadd_windowed` (module docstring)."""
    n = fit.tile_rows * fit.tile_cols
    return 4 * (2 * SLOT_GROUP * fit.win_m * SLOT_GROUP * fit.win_l
                + 2 * fit.win_l * n + 3 * fit.win_m * n + 16 * 8 * n)


def _tile_spans(grid_wcs, frame_wcs, npix: int, tr: int, tc: int):
    """Largest (x, y) source span of a tr x tc output tile over nine sample
    tiles of the grid and every frame, through the float64 TAN chain."""
    r0 = np.array([0, (npix // tr // 2) * tr, npix - tr], np.float64)
    c0 = np.array([0, (npix // tc // 2) * tc, npix - tc], np.float64)
    ys = (r0[:, None, None] + np.array([0, 0, tr - 1, tr - 1])).repeat(3, 1)
    xs = (c0[None, :, None] + np.array([0, tc - 1, 0, tc - 1])).repeat(3, 0)
    ra, dec = geometry.pixel_to_sky(xs.reshape(9, 4), ys.reshape(9, 4),
                                    np.asarray(grid_wcs, np.float64))
    with np.errstate(all="ignore"):
        sx, sy = geometry.sky_to_pixel(ra, dec, frame_wcs.T[:, :, None, None])
    return tuple(float((a.max(-1) - a.min(-1)).max()) for a in (sx, sy))


def window_fit(grid_wcs, frame_wcs, npix: int, h: int, w: int, cap: int,
               lanes: Optional[str]) -> Optional[WindowFit]:
    """The windowed scan's static shape for a plan, or None (keep the gather).

    ``grid_wcs`` is the query grid's WCS vector, ``frame_wcs`` the (N, 8)
    WCS vectors of the frames the plan gates, ``h`` x ``w`` the frame
    shape, ``cap`` the pack capacity and ``lanes`` the frame axis on the
    lanes (`lane_axis`).  The rule is the module docstring's.  The lane
    axis must be whole 128-lane tiles (a DMA cannot cut a padded lane
    tile); a frame no longer than ``WIN_L`` along it is read whole.  A
    window DMA carries a whole 8-slot tile row, so ``cap`` is a multiple
    of 8; and the step must fit ``VMEM_LIMIT_BYTES``.
    """
    fw = np.asarray(frame_wcs, np.float64).reshape(-1, 8)
    if lanes not in _VIEWS or cap % SLOT_GROUP or len(fw) == 0:
        return None
    ext_m, ext_l = (w, h) if lanes == "y" else (h, w)
    det = fw[:, 4] * fw[:, 7] - fw[:, 5] * fw[:, 6]
    if ext_l % LANES or not np.isfinite(fw).all() or np.any(det == 0):
        return None
    win_l = min(ext_l, WIN_L)
    fits = []
    for tr, tc in TILES:
        if npix % tr or npix % tc:
            continue
        sx, sy = _tile_spans(grid_wcs, fw, npix, tr, tc)
        span_m, span_l = (sx, sy) if lanes == "y" else (sy, sx)
        need_m, need_l = span_m * 1.01 + 5.0, span_l * 1.01 + 5.0
        if not (np.isfinite(need_m) and np.isfinite(need_l)):
            continue
        if ext_l > WIN_L and need_l + (LANES - 1) > WIN_L:
            continue
        wm = next((b for b in WIN_M_BUCKETS if need_m <= b or b >= ext_m),
                  None)
        if wm is None:
            continue
        fit = WindowFit(tr, tc, min(wm, ext_m), win_l, lanes)
        if windowed_vmem_bytes(fit) <= VMEM_LIMIT_BYTES:
            fits.append(fit)
    return min(fits, key=lambda f: f.win_m, default=None)


def common_fit(fits: Sequence[Optional[WindowFit]]) -> Optional[WindowFit]:
    """One static fit for several plans scanned in one program: the widest
    window, where every plan fits with the same tile and lane axis.  The
    window's width does not change a sample's bits: every tap outside the
    two weighted ones adds an exact zero."""
    if not fits or any(f is None for f in fits):
        return None
    if len({(f.tile_rows, f.tile_cols, f.win_l, f.lanes) for f in fits}) > 1:
        return None
    return max(fits, key=lambda f: f.win_m)


def _tile_major(a, tr: int, tc: int):
    """(Q, Q) -> (n_tiles, 1, tr*tc): tiles row-major, pixels row-major."""
    q = a.shape[0]
    return (a.astype(jnp.float32).reshape(q // tr, tr, q // tc, tc)
            .transpose(0, 2, 1, 3).reshape(-1, 1, tr * tc))


def _untile(a, q: int, tr: int, tc: int):
    return a.reshape(q // tr, q // tc, tr, tc).transpose(0, 2, 1, 3).reshape(q, q)


def _window_origins(recs, grid_ra, grid_dec, fit: WindowFit, h: int, w: int):
    """Window origins and the guard (module docstring).

    Returns ``(origins, covered)``: (2 * cap * n_tiles,) int32, major-axis
    origins slot-major then lane-axis origins; and a scalar bool, whether
    every accepted slot's window holds its taps over every tile.  A slot
    whose corners are all non-finite (an all-zero WCS) samples nothing and
    reads origin 0; one with only some non-finite corners is not covered.
    ``recs`` are the pack's `_image_records`: the corners go through the
    kernel's own projection."""
    tr, tc = fit.tile_rows, fit.tile_cols
    q = grid_ra.shape[0]
    rows = np.arange(0, q, tr)
    cols = np.arange(0, q, tc)
    ry = np.stack([rows, rows, rows + tr - 1, rows + tr - 1], -1)   # (nr, 4)
    cx = np.stack([cols, cols + tc - 1, cols, cols + tc - 1], -1)   # (nc, 4)
    ri = np.broadcast_to(ry[:, None, :], (len(rows), len(cols), 4)).reshape(-1, 4)
    ci = np.broadcast_to(cx[None, :, :], (len(rows), len(cols), 4)).reshape(-1, 4)
    sx, sy = _sky_to_pixel(grid_ra[ri, ci][None], grid_dec[ri, ci][None],
                           lambda k: recs[:, k, None, None])       # (cap, nt, 4)
    sm, sl = (sx, sy) if fit.lanes == "y" else (sy, sx)
    ext_m, ext_l = (w, h) if fit.lanes == "y" else (h, w)
    finite = jnp.isfinite(sm) & jnp.isfinite(sl)

    def axis(c, ext, win, align):
        c = jnp.where(finite, c, 0.0)
        org = jnp.clip(jnp.floor(jnp.min(c, axis=-1)) - 1.0, 0, ext - win)
        if align and ext > win:
            org = jnp.floor(org / LANES) * LANES
        need = jnp.clip(jnp.floor(jnp.max(c, axis=-1)) + 2.0, 0, ext - 1)
        return org.astype(jnp.int32), need <= org + (win - 1)

    m0, m_ok = axis(sm, ext_m, fit.win_m, False)
    l0, l_ok = axis(sl, ext_l, fit.win_l, True)
    ok = (m_ok & l_ok & finite.all(-1)) | ~finite.any(-1)          # (cap, nt)
    covered = jnp.all(ok | (recs[:, 0:1] == 0))
    return jnp.concatenate([m0.reshape(-1), l0.reshape(-1)]), covered


def _windowed_kernel(pack_ref, org_ref, rec_ref, gra_ref, gdec_ref, pix_hbm,
                     coadd_ref, depth_ref, buf, sem, *, cap, n_tiles, h, w,
                     lanes):
    t = pl.program_id(0)
    g = pl.program_id(1)
    groups = pl.num_programs(1)
    k = t * groups + g
    grp, wm, wl = buf.shape[1], buf.shape[2], buf.shape[4]
    ext_m, ext_l = (w, h) if lanes == "y" else (h, w)

    def origin(tt, s):
        return org_ref[s * n_tiles + tt], org_ref[(cap + s) * n_tiles + tt]

    def copies(kk, b):
        """The group's windows: slot j's window of its frame, with the
        other slots of its 8-slot tile row riding along (unread)."""
        tt, gg = kk // groups, kk % groups
        out = []
        s0 = pl.multiple_of(gg * grp, grp)
        for j in range(grp):
            m0, l0 = origin(tt, gg * grp + j)
            src = pix_hbm.at[pack_ref[0], pl.ds(m0, wm), pl.ds(s0, grp)]
            if wl < ext_l:
                # `_window_origins` aligns lane origins to 128; a frame no
                # longer than the window along the lanes is read whole.
                src = src.at[:, :, pl.ds(pl.multiple_of(l0, LANES), wl)]
            out.append(pltpu.make_async_copy(src, buf.at[b, j], sem.at[b, j]))
        return out

    b = k % 2

    @pl.when(k == 0)
    def _first():
        for c in copies(k, b):
            c.start()

    # Double buffer: the next step's windows stream in during this one.
    @pl.when(k + 1 < n_tiles * groups)
    def _next():
        for c in copies(k + 1, 1 - b):
            c.start()

    for c in copies(k, b):
        c.wait()

    gra = gra_ref[0]
    gdec = gdec_ref[0]
    n = gra.shape[1]
    li = jax.lax.broadcasted_iota(jnp.int32, (wl, n), 0)
    mi = jax.lax.broadcasted_iota(jnp.int32, (wm, n), 0)
    c_sum = jnp.zeros((1, n), jnp.float32)
    d_sum = jnp.zeros((1, n), jnp.float32)
    for j in range(grp):
        s = g * grp + j
        m0, l0 = origin(t, s)
        sx, sy = _sky_to_pixel(gra, gdec, lambda q: rec_ref[s, q])
        sm, sl = (sx, sy) if lanes == "y" else (sy, sx)
        mf = jnp.floor(sm)
        lf = jnp.floor(sl)
        dm = sm - mf
        dl = sl - lf
        m_lo = jnp.clip(mf.astype(jnp.int32), 0, ext_m - 1) - m0
        m_hi = jnp.clip(mf.astype(jnp.int32) + 1, 0, ext_m - 1) - m0
        l_lo = jnp.clip(lf.astype(jnp.int32), 0, ext_l - 1) - l0
        l_hi = jnp.clip(lf.astype(jnp.int32) + 1, 0, ext_l - 1) - l0
        w_l = (jnp.where(li == l_lo, 1.0 - dl, 0.0)
               + jnp.where(li == l_hi, dl, 0.0))
        # MXU: window (wm, wl) @ lane weights (wl, n) -> (wm, n).
        vals = jnp.dot(buf[b, j, :, j, :], w_l, precision=_HIGHEST,
                       preferred_element_type=jnp.float32)
        w_m = (jnp.where(mi == m_lo, 1.0 - dm, 0.0)
               + jnp.where(mi == m_hi, dm, 0.0))
        val = jnp.sum(w_m * vals, axis=0, keepdims=True)
        inside = (sx >= 0) & (sx <= w - 1) & (sy >= 0) & (sy <= h - 1)
        a = rec_ref[s, 0]
        # A select, not val * mask: an empty slot (all-zero WCS) projects
        # to NaN coordinates, and NaN * 0 is NaN on the TPU.
        c_sum = c_sum + jnp.where(inside, val, 0.0) * a
        d_sum = d_sum + inside.astype(jnp.float32) * a

    @pl.when(g == 0)
    def _init():
        coadd_ref[0] = c_sum
        depth_ref[0] = d_sum

    @pl.when(g > 0)
    def _accum():
        coadd_ref[0] += c_sum
        depth_ref[0] += d_sum


def coadd_windowed(
    pixels: jnp.ndarray,    # (P, cap, H, W) resident layout
    pack,                   # scalar int32: the pack to warp
    wcs_vecs: jnp.ndarray,  # (cap, 8) the pack's WCS vectors
    accepts: jnp.ndarray,   # (cap,)
    grid_ra: jnp.ndarray,   # (Q, Q)
    grid_dec: jnp.ndarray,  # (Q, Q)
    *,
    fit: WindowFit,
    interpret,
):
    """One pack's (coadd, depth, covered) on the (Q, Q) grid (module doc).

    ``covered`` is int32 1 when every accepted slot's windows held all its
    taps, 0 when the guard found one outside: (coadd, depth) are then
    short of those taps, and the caller must not use them."""
    _, cap, h, w = pixels.shape
    q = grid_ra.shape[0]
    tr, tc = fit.tile_rows, fit.tile_cols
    n = tr * tc
    n_tiles = (q // tr) * (q // tc)
    recs = _image_records(wcs_vecs, accepts)
    origins, covered = _window_origins(recs, grid_ra, grid_dec, fit, h, w)
    row = pl.BlockSpec((1, 1, n), lambda t, s, *_: (t, 0, 0))

    out = pl.pallas_call(
        functools.partial(_windowed_kernel, cap=cap, n_tiles=n_tiles,
                          h=h, w=w, lanes=fit.lanes),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(n_tiles, cap // SLOT_GROUP),
            in_specs=[_SMEM, row, row,
                      pl.BlockSpec(memory_space=pltpu.HBM)],
            out_specs=[row, row],
            scratch_shapes=[
                pltpu.VMEM((2, SLOT_GROUP, fit.win_m, SLOT_GROUP,
                            fit.win_l), jnp.float32),
                pltpu.SemaphoreType.DMA((2, SLOT_GROUP)),
            ],
        ),
        out_shape=[jax.ShapeDtypeStruct((n_tiles, 1, n), jnp.float32)] * 2,
        # The window DMAs chain from one grid step to the next: sequential.
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(
        jnp.reshape(pack, (1,)).astype(jnp.int32),
        origins,
        recs,
        _tile_major(grid_ra, tr, tc),
        _tile_major(grid_dec, tr, tc),
        jnp.transpose(pixels.astype(jnp.float32), _VIEWS[fit.lanes]),
    )
    coadd, depth = (_untile(o, q, tr, tc) for o in out)
    return coadd, depth, covered.astype(jnp.int32)
