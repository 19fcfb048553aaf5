"""PSF matching and homogenization (the paper deferred it — footnote 2).

Before stacking, exposures taken in different seeing should be convolved to
a common (worst) PSF so the coadd has a well-defined point-spread function.
Two regimes, one bank contract:

* **Gaussian-to-Gaussian** (`matching_kernel_bank`): if an image has PSF
  sigma_i and the target is sigma_t >= sigma_i, convolving with a Gaussian
  of sigma_k = sqrt(sigma_t^2 - sigma_i^2) matches them exactly (Gaussians
  are closed under convolution).  Separable — one (K,) row per slot.

* **Measured-PSF homogenization** (`homogenization_bank`): production
  co-addition can't assume Gaussian optics; each exposure carries an
  *empirical* PSF stamp (survey.py synthesizes elliptical Moffats).  The
  Lupton-style matching kernel k solving ``stamp * k = target`` is found by
  regularized least squares in Fourier space — a ridge term keeps the
  effective deconvolution bounded where the stamp's transform runs out of
  power — then cropped to a static (K, K) tap grid and renormalized to unit
  sum (flux conservation).  Stamps already broader than the target clamp to
  delta rows with a warning: matching *never deconvolves* (monotone).  One
  non-separable (K, K) kernel per slot.

Because per-image kernels vary but jit demands static shapes, the engine
host-precomputes the bank — delta rows where no widening is needed — and
passes it to the map stage as a plain operand, in both the XLA path
(`convolve_batch`, which dispatches on bank rank: (N, K) separable rows vs
(N, K, K) full 2-D taps) and the Pallas `coadd_fused` kernel (in-kernel
banded-matmul convolution; 1-D and 2-D variants).  All paths share one
convention — cross-correlation with edge-clamped sampling:
``out[i, j] = sum_{m,n} k[m, n] * img[clip(i+m-r), clip(j+n-r)]``.
"""

from __future__ import annotations

import warnings

import jax
import jax.numpy as jnp
import numpy as np


def gaussian_kernel_1d(sigma: float, radius: int | None = None) -> jnp.ndarray:
    if sigma <= 0:
        return jnp.ones((1,), jnp.float32)
    if radius is None:
        radius = max(1, int(np.ceil(3.0 * sigma)))
    x = jnp.arange(-radius, radius + 1, dtype=jnp.float32)
    k = jnp.exp(-0.5 * (x / sigma) ** 2)
    return k / k.sum()


def matching_kernel_bank(
    psf_sigmas: np.ndarray, sigma_target: float, radius: int | None = None
) -> np.ndarray:
    """Per-slot 1-D matching kernels, one static-width bank for a dataset.

    ``psf_sigmas`` is any-shaped (...,) array of per-image PSF widths; the
    result is (..., K) with K = 2*radius + 1 shared across slots (static
    shapes for jit / Pallas operands).  Slots already at/above the target
    (and empty slots with sigma 0 treated alike) get an exact delta row, so
    applying the bank is a no-op for them — the "no-op when
    sigma_target <= sigma_image" rule of `match_psf`, vectorized.
    """
    s = np.asarray(psf_sigmas, np.float64)
    # sigma <= 0 marks an empty/padded slot, not an infinitely sharp image:
    # give it a delta row and keep it out of the bank-radius computation so
    # phantom slots can't widen K for the whole layout.
    sig_k = np.where(
        s > 0, np.sqrt(np.maximum(sigma_target**2 - s**2, 0.0)), 0.0
    )
    if radius is None:
        radius = int(np.ceil(3.0 * float(sig_k.max(initial=0.0))))
    k_width = 2 * radius + 1
    x = np.arange(-radius, radius + 1, dtype=np.float64)
    delta = (x == 0).astype(np.float64)
    with np.errstate(divide="ignore", invalid="ignore"):
        g = np.exp(-0.5 * (x / np.where(sig_k == 0, 1.0, sig_k)[..., None]) ** 2)
    bank = np.where((sig_k > 0)[..., None], g, delta)
    bank = bank / bank.sum(axis=-1, keepdims=True)
    assert bank.shape == s.shape + (k_width,)
    return bank.astype(np.float32)


def convolve_separable(image: jnp.ndarray, kernel: jnp.ndarray) -> jnp.ndarray:
    """(H, W) image * 1-D kernel applied along both axes (edge-padded)."""
    r = (kernel.shape[0] - 1) // 2

    def conv1d(row):
        return jnp.convolve(jnp.pad(row, (r, r), mode="edge"), kernel,
                            mode="valid", precision=jax.lax.Precision.HIGHEST)

    out = jax.vmap(conv1d)(image)          # rows
    out = jax.vmap(conv1d)(out.T).T        # cols
    return out


def convolve_batch(images: jnp.ndarray, kernels: jnp.ndarray) -> jnp.ndarray:
    """(N, H, W) images, each convolved with its own per-slot kernel.

    Dispatches on bank rank: (N, K) rows from `matching_kernel_bank` apply
    separably; (N, K, K) taps from `homogenization_bank` apply as full 2-D
    correlations (`convolve_2d`).  A delta row makes the convolution exact
    identity up to float rounding.  K == 1 (a bank with zero max radius,
    i.e. nothing to widen) short-circuits to a multiply.
    """
    if kernels.ndim == images.ndim:  # (N, K, K) measured-PSF bank
        if kernels.shape[-1] == 1:
            return images * kernels[..., 0, 0][:, None, None]
        return jax.vmap(convolve_2d)(images, kernels)
    if kernels.shape[-1] == 1:
        return images * kernels[..., 0][:, None, None]
    return jax.vmap(convolve_separable)(images, kernels)


def match_psf(image: jnp.ndarray, sigma_image: float, sigma_target: float) -> jnp.ndarray:
    """Convolve to the target PSF. No-op if already at/above target width."""
    if sigma_target <= sigma_image:
        return image
    sigma_k = float(np.sqrt(sigma_target**2 - sigma_image**2))
    return convolve_separable(image, gaussian_kernel_1d(sigma_k))


# ----- measured-PSF homogenization (Lupton-style, paper footnote 2) -----

def gaussian_stamp(sigma: float, size: int) -> np.ndarray:
    """(size, size) unit-sum circular Gaussian — the homogenization target."""
    if size % 2 == 0:
        raise ValueError(f"stamp size must be odd, got {size}")
    c = (size - 1) / 2.0
    yy, xx = np.mgrid[0:size, 0:size].astype(np.float64)
    g = np.exp(-0.5 * ((xx - c) ** 2 + (yy - c) ** 2) / max(sigma, 1e-6) ** 2)
    return (g / g.sum()).astype(np.float64)


def stamp_sigma(stamps: np.ndarray) -> np.ndarray:
    """Gaussian-equivalent width per stamp from second moments.

    ``stamps`` is (..., S, S); the result is (...,).  The radially averaged
    second moment sqrt(<r^2>/2) equals sigma exactly for a Gaussian and is
    the honest scalar width for anything else (elliptical Moffats included)
    — it is what the monotonicity clamp compares against the target.
    Zero-sum (empty-slot) stamps report width 0.
    """
    s = np.asarray(stamps, np.float64)
    size = s.shape[-1]
    c = (size - 1) / 2.0
    yy, xx = np.mgrid[0:size, 0:size].astype(np.float64)
    r2 = (xx - c) ** 2 + (yy - c) ** 2
    tot = s.sum(axis=(-2, -1))
    mom = (s * r2).sum(axis=(-2, -1))
    with np.errstate(divide="ignore", invalid="ignore"):
        sig = np.sqrt(np.maximum(mom / np.where(tot == 0, 1.0, tot), 0.0) / 2.0)
    return np.where(tot > 0, sig, 0.0)


def _delta_stamp(size: int) -> np.ndarray:
    d = np.zeros((size, size), np.float64)
    d[(size - 1) // 2, (size - 1) // 2] = 1.0
    return d


def homogenization_kernel(
    stamp: np.ndarray, target: np.ndarray, ridge: float = 1e-6
) -> np.ndarray:
    """Solve ``stamp * k = target`` for one (S, S) matching kernel.

    Regularized least squares in Fourier space: with hats the (zero-padded,
    linear-convolution-sized) transforms, the minimizer of
    ``||k * stamp - target||^2 + lam ||k||^2`` is
    ``K = conj(S) T / (|S|^2 + lam)`` with ``lam = ridge * max|S|^2`` —
    the ridge bounds the effective deconvolution where the stamp's transform
    runs out of power, which is what keeps measured (noisy-tailed) PSFs from
    amplifying into ringing kernels.  The solve uses the *convolution*
    convention; the returned kernel is flipped so applying it with the
    runtime correlation op (`convolve_2d` / the Pallas banded matmuls)
    realizes the fit.  Unit-sum normalized: matching conserves flux exactly.
    """
    s = np.asarray(stamp, np.float64)
    t = np.asarray(target, np.float64)
    size = s.shape[-1]
    # Odd linear-convolution size: no wraparound inside the crop, and the
    # stamp center sits exactly on the (i)fftshift origin at (n-1)/2.
    n = 2 * size - 1
    s_hat = np.fft.fft2(np.fft.ifftshift(_center_embed(s, n)))
    t_hat = np.fft.fft2(np.fft.ifftshift(_center_embed(t, n)))
    power = np.abs(s_hat) ** 2
    lam = ridge * power.max()
    k_hat = np.conj(s_hat) * t_hat / (power + lam)
    k_full = np.fft.fftshift(np.fft.ifft2(k_hat).real)
    lo = (n - size) // 2
    k = k_full[lo : lo + size, lo : lo + size]
    k = k[::-1, ::-1]  # convolution solve -> correlation-convention taps
    tot = k.sum()
    if abs(tot) < 1e-8:
        return _delta_stamp(size)
    return k / tot


def _center_embed(stamp: np.ndarray, n: int) -> np.ndarray:
    """Place an (S, S) stamp at the center of an (n, n) zero canvas."""
    size = stamp.shape[-1]
    out = np.zeros((n, n), np.float64)
    lo = (n - size) // 2
    out[lo : lo + size, lo : lo + size] = stamp
    return out


def homogenization_bank(
    stamps: np.ndarray,
    psf_sigmas: np.ndarray,
    sigma_target: float,
    ridge: float = 1e-6,
    clamp_tol: float = 1.02,
) -> np.ndarray:
    """Per-slot 2-D matching kernels from measured PSF stamps.

    ``stamps`` is (..., S, S) — any leading slot shape, e.g. the seqfile
    (P, cap) grid — and the result is (..., S, S) float32: one non-separable
    correlation kernel per slot taking that slot's measured PSF to a
    circular Gaussian of ``sigma_target``.  The static tap width S is shared
    across the bank (jit/Pallas operand contract, like `matching_kernel_bank`).

    Empty slots (``psf_sigmas <= 0`` or zero-sum stamps) get exact delta
    rows.  Slots whose *measured* width already exceeds the target get delta
    rows too — matching is monotone, it never deconvolves — and the bank
    warns once with the clamp count so a mis-chosen target is loud rather
    than silently sharpening.
    """
    s = np.asarray(stamps, np.float64)
    if s.shape[-1] != s.shape[-2] or s.shape[-1] % 2 == 0:
        raise ValueError(f"stamps must be odd square, got {s.shape[-2:]}")
    size = s.shape[-1]
    lead = s.shape[:-2]
    sig = np.asarray(psf_sigmas, np.float64).reshape(-1)
    flat = s.reshape((-1, size, size))
    target = gaussian_stamp(sigma_target, size)
    delta = _delta_stamp(size)
    widths = stamp_sigma(flat)
    empty = (sig <= 0) | (flat.sum(axis=(-2, -1)) <= 0)
    too_wide = ~empty & (widths > clamp_tol * float(stamp_sigma(target)))
    out = np.broadcast_to(delta, flat.shape).copy()
    ok = ~(empty | too_wide)
    if ok.any():
        # Batched form of `homogenization_kernel` — same math, one FFT call
        # over all live slots instead of a per-slot Python loop (a layout
        # is P*cap slots; production archives make the loop the bottleneck).
        n = 2 * size - 1
        lo = (n - size) // 2
        emb = np.zeros((int(ok.sum()), n, n), np.float64)
        emb[:, lo : lo + size, lo : lo + size] = flat[ok]
        s_hat = np.fft.fft2(np.fft.ifftshift(emb, axes=(-2, -1)))
        t_hat = np.fft.fft2(np.fft.ifftshift(_center_embed(target, n)))
        power = np.abs(s_hat) ** 2
        lam = ridge * power.max(axis=(-2, -1), keepdims=True)
        k_hat = np.conj(s_hat) * t_hat[None] / (power + lam)
        k_full = np.fft.fftshift(np.fft.ifft2(k_hat).real, axes=(-2, -1))
        k = k_full[:, lo : lo + size, lo : lo + size][:, ::-1, ::-1]
        tot = k.sum(axis=(-2, -1), keepdims=True)
        k = np.where(np.abs(tot) < 1e-8, delta, k / np.where(tot == 0, 1.0, tot))
        out[ok] = k
    if too_wide.any():
        warnings.warn(
            f"homogenization_bank: {int(too_wide.sum())}/{len(flat)} stamps "
            f"wider than target sigma={sigma_target}; clamped to delta "
            "(matching never deconvolves)",
            RuntimeWarning,
            stacklevel=2,
        )
    return out.reshape(lead + (size, size)).astype(np.float32)


def convolve_2d(image: jnp.ndarray, kernel: jnp.ndarray) -> jnp.ndarray:
    """(H, W) image correlated with one (K, K) kernel, edge-clamped.

    ``out[i, j] = sum_{m,n} kernel[m, n] * image[clip(i+m-r), clip(j+n-r)]``
    — edge padding makes the clip; `lax.conv_general_dilated` is already a
    cross-correlation, so the taps apply unflipped, exactly like the Pallas
    2-D banded-matmul variant (`warp._convolve_2d_matmul`).  HIGHEST
    precision keeps the TPU from rounding pixels to bf16 in the MXU (its
    default for float32), as in every other convolution here.
    """
    kh, kw = kernel.shape
    padded = jnp.pad(
        image, (((kh - 1) // 2,) * 2, ((kw - 1) // 2,) * 2), mode="edge"
    )
    out = jax.lax.conv_general_dilated(
        padded[None, None].astype(jnp.float32),
        kernel[None, None].astype(jnp.float32),
        window_strides=(1, 1),
        padding="VALID",
        precision=jax.lax.Precision.HIGHEST,
    )
    return out[0, 0]
