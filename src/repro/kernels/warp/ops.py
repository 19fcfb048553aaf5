"""Jitted public wrappers for the warp kernels.

Interpret mode is never chosen by the caller: `repro.kernels.interpret_mode`
turns it on for the CPU backend (the test environment) and off everywhere
else, so on a TPU the same BlockSpecs always lower through Mosaic.
``block_rows=None`` autotunes the output block to the frame and grid sizes
(and refuses frames too large for VMEM).
"""

from __future__ import annotations

from functools import partial

import jax

from repro.kernels import interpret_mode
from repro.kernels.warp.warp import autotune_block_rows
from repro.kernels.warp.warp import coadd_clip as _coadd_clip
from repro.kernels.warp.warp import coadd_fused as _coadd_fused
from repro.kernels.warp.warp import coadd_hist as _coadd_hist
from repro.kernels.warp.warp import coadd_moments as _coadd_moments
from repro.kernels.warp.warp import mosaic_bricks as _mosaic_bricks
from repro.kernels.warp import windowed as _windowed


def _rows(block_rows, pixels, grid_ra, psf_kernels):
    if block_rows is not None:
        return block_rows
    return autotune_block_rows(
        grid_ra.shape[0], pixels.shape[1], pixels.shape[2],
        psf_kernel_width=0 if psf_kernels is None else psf_kernels.shape[-1],
        psf_kernel_2d=psf_kernels is not None and psf_kernels.ndim == 3,
    )


@partial(jax.jit, static_argnames=("block_rows",))
def coadd_fused(pixels, wcs_vecs, accepts, grid_ra, grid_dec, psf_kernels=None,
                block_rows=None):
    """Fused map+reduce: (N,H,W) images -> (Q,Q) coadd + depth.

    ``psf_kernels`` (N, K) or (N, K, K), when given, PSF-matches each image
    inside the kernel before warping (banded-matmul convolution).
    """
    return _coadd_fused(
        pixels, wcs_vecs, accepts, grid_ra, grid_dec, psf_kernels=psf_kernels,
        block_rows=_rows(block_rows, pixels, grid_ra, psf_kernels),
        interpret=interpret_mode(),
    )


@partial(jax.jit, static_argnames=("block_rows",))
def coadd_moments(pixels, wcs_vecs, accepts, grid_ra, grid_dec,
                  psf_kernels=None, block_rows=None):
    """Fused robust pass 1: (N,H,W) images -> (S0, S1, S2) moment maps."""
    return _coadd_moments(
        pixels, wcs_vecs, accepts, grid_ra, grid_dec, psf_kernels=psf_kernels,
        block_rows=_rows(block_rows, pixels, grid_ra, psf_kernels),
        interpret=interpret_mode(),
    )


@partial(jax.jit, static_argnames=("block_rows",))
def coadd_clip(pixels, wcs_vecs, accepts, grid_ra, grid_dec, center, thresh,
               psf_kernels=None, block_rows=None):
    """Fused robust final pass: accumulate samples inside the clip window."""
    return _coadd_clip(
        pixels, wcs_vecs, accepts, grid_ra, grid_dec, center, thresh,
        psf_kernels=psf_kernels,
        block_rows=_rows(block_rows, pixels, grid_ra, psf_kernels),
        interpret=interpret_mode(),
    )


@partial(jax.jit, static_argnames=("nbins", "block_rows"))
def coadd_hist(pixels, wcs_vecs, accepts, grid_ra, grid_dec, lo, inv_w,
               nbins=16, psf_kernels=None, block_rows=None):
    """Fused median round 1: (nbins, Q, Q) weighted binapprox histogram."""
    return _coadd_hist(
        pixels, wcs_vecs, accepts, grid_ra, grid_dec, lo, inv_w, nbins=nbins,
        psf_kernels=psf_kernels,
        block_rows=_rows(block_rows, pixels, grid_ra, psf_kernels),
        interpret=interpret_mode(),
    )


def coadd_windowed(pixels, pack, wcs_vecs, accepts, grid_ra, grid_dec, fit):
    """Windowed map+reduce of resident pack ``pack`` of (P,cap,H,W) pixels
    -> (Q,Q) coadd + depth + whether the windows held every tap; ``fit``
    from `windowed.window_fit`.  Traced inside the caller's scan."""
    return _windowed.coadd_windowed(pixels, pack, wcs_vecs, accepts, grid_ra,
                                    grid_dec, fit=fit,
                                    interpret=interpret_mode())


@partial(jax.jit, static_argnames=("npix",))
def mosaic_bricks(tiles, covs, offsets, npix):
    """(B,bh,bw) cached brick tiles + weights -> (npix,npix) coadd + depth."""
    return _mosaic_bricks(tiles, covs, offsets, npix, interpret=interpret_mode())
