"""Pallas TPU kernels: fused inverse-warp + reduce (the paper's mapper hot spot).

Hardware adaptation (DESIGN.md §2): the mapper's projection is a bilinear
*gather* — the classic GPU formulation (one thread per output pixel doing
random-access texture reads) has no TPU analogue, since the VPU wants dense
vectors and the MXU wants matmuls.  We therefore reformulate the gather as
structured dense algebra:

  1. For a block of output pixels, compute source coordinates (sx, sy) on
     the VPU (gnomonic trig is elementwise).
  2. **Row gather as matmul**: image^T @ onehot(y) puts the two needed
     source rows of every output pixel into a (W, pixels) operand via the
     MXU — gathers become dense matmuls.
  3. **Column select as masked reduction**: v = sum(rows * onehot(x),
     axis=0) on the VPU.
  4. Bilinear combine + acceptance gating (the Algorithm-2 filter is one
     multiply — "discarding false positives is cheap", paper §4.1.4).

Layout rules Mosaic enforces, and how the kernels meet them:

* Output pixels ride the *lane* axis.  The (Q, Q) query grid and every
  per-pixel operand or output are handed to the kernel as a (1, Q*Q) row,
  blocked (1, block_rows*Q); the block must be a multiple of 128 lanes
  (or the whole grid).  Step 2's one-hot is then (H, pixels) and its
  matmul contracts the image's row axis, so no in-kernel reshape of a
  vector is ever needed (Mosaic cannot cast a (8, Q) tile to a 1-D vector).
* Per-image scalars (acceptance gate, TAN projection constants, PSF
  matching taps, brick offsets) live whole in SMEM as (images, values)
  tables, indexed by the image grid coordinate.  Two-dimensional, so that
  a vmapped call (a batch of queries) blocks them as whole (rows, values)
  slices, which Mosaic accepts.  Everything that depends only on
  the image — sin/cos of the tangent point, the CD determinant — is
  computed once in XLA before the call: the TPU's scalar unit has no
  transcendentals or divide.
* Every matmul runs at ``Precision.HIGHEST``.  A one-hot gather is then
  exact (the f32 value splits into three bf16 terms, each multiplied by 1),
  and the PSF band matmuls keep f32 accuracy; the TPU default would round
  the image to bf16 first.

Kernels (all with grid (row_block, image), images innermost, accumulating
into the output block across grid steps — the matmul-k-loop idiom — so the
(N, Q, Q) stack of projected tiles never materializes in HBM):

* ``coadd_fused``   — Algorithm 1: coadd + depth.
* ``coadd_moments`` / ``coadd_hist`` / ``coadd_clip`` — the robust passes.
* ``mosaic_bricks`` — merge cached brick tiles into a query canvas.

Optional in-kernel PSF matching convolves each image before the warp with
a separable (K,) row or a full (Kh, Kw) tap grid, as banded matmuls.

VMEM budget per grid step: image (H*W*4, double-buffered) + onehot row
gathers (pixels*H*4) + gathered rows and column masks (pixels*W*4) + pixel
rows; ``autotune_block_rows`` picks the block and refuses frames that do
not fit.

These kernels hold a whole frame per grid step and contract over all H
rows per output pixel (2*H*W multiply-adds, ~6 M at 2048x1489), so SDSS
frames do not fit them.  The mean scan at such sizes runs the windowed
sampler of `repro.kernels.warp.windowed` instead, which copies only each
output tile's source window into VMEM (its own VMEM budget and window-fit
rule are documented there).  This lane stays whole-frame for now: windowing
it means the same DMA machinery in four kernel bodies plus a halo for the
in-kernel PSF convolution, and no benchmark cell runs it yet.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEG2RAD = float(jnp.pi / 180.0)
RAD2DEG = float(180.0 / jnp.pi)

LANES = 128
_HIGHEST = jax.lax.Precision.HIGHEST
_SMEM = pl.BlockSpec(memory_space=pltpu.SMEM)


def _lane_ok(q: int, block_rows: int) -> bool:
    return (block_rows * q) % LANES == 0 or block_rows == q


def autotune_block_rows(
    q: int,
    h: int,
    w: int,
    vmem_budget_bytes: int = 4 << 20,
    candidates=(128, 64, 48, 32, 24, 16, 12, 8, 6, 4, 3, 2, 1),
    psf_kernel_width: int = 0,
    psf_kernel_2d: bool = False,
) -> int:
    """Largest ``block_rows`` dividing ``q`` whose grid step fits the budget.

    A block must also span a multiple of 128 output pixels (lanes) or the
    whole grid.  Per-step VMEM for the fused kernels (DESIGN.md §2), with
    n = block_rows*q output pixels: the source image, double-buffered
    (2*h*w); two onehot row-gather operands (2*n*h); two gathered row
    blocks and two column masks (4*n*w); and the per-pixel rows, which a
    (1, n) layout pads to 8 sublanes (~64*n) — all float32.  When the
    PSF-matching variant runs (``psf_kernel_width`` > 1), each step also
    holds the (h, h) and (w, w) band matrices and the convolved image copy;
    the 2-D variant (``psf_kernel_2d``) adds the accumulating output copy.

    Raises ``ValueError`` when no block fits: the warp holds one whole
    source frame per grid step, so a frame larger than the budget cannot
    run through these kernels at all.
    """
    if psf_kernel_width > 1 and psf_kernel_2d:
        psf_bytes = 4 * (h * h + w * w + 2 * h * w)
    elif psf_kernel_width > 1:
        psf_bytes = 4 * (h * h + w * w + h * w)
    else:
        psf_bytes = 0
    for b in sorted({q, *candidates}, reverse=True):
        if b > q or q % b or not _lane_ok(q, b):
            continue
        n = b * q
        step_bytes = 4 * (2 * h * w + 2 * n * h + 4 * n * w + 64 * n) + psf_bytes
        if step_bytes <= vmem_budget_bytes:
            return b
    raise ValueError(
        f"a {h}x{w} frame does not fit the Pallas warp at npix={q}: every "
        f"block of output rows needs more than {vmem_budget_bytes} bytes of "
        "VMEM (one whole source frame per grid step); use the XLA lane"
    )


def _pixel_block(q: int, block_rows: int) -> int:
    """Output pixels per grid step, checked against the lane tiling."""
    if q % block_rows:
        raise ValueError(f"npix {q} must be a multiple of block_rows {block_rows}")
    if not _lane_ok(q, block_rows):
        raise ValueError(
            f"block_rows={block_rows} at npix={q} gives {block_rows * q} output "
            f"pixels per grid step; the TPU needs a multiple of {LANES} "
            "(or the whole grid)"
        )
    return block_rows * q


def _as_row(a):
    """(Q, Q) per-pixel array -> the kernels' (1, Q*Q) lane-major row."""
    return a.astype(jnp.float32).reshape(1, -1)


def _row_spec(bn: int):
    return pl.BlockSpec((1, bn), lambda r, i: (0, r))


def _image_records(wcs_vecs, accepts):
    """(N, 11) SMEM records: [accept, ra0, sin(dec0), cos(dec0), x0, y0,
    cd11, cd12, cd21, cd22, det(cd)] per image.

    Same float32 operations as `geometry.sky_to_pixel` computes per image,
    so the kernel's projection matches the XLA mapper's.
    """
    w = wcs_vecs.astype(jnp.float32)
    dec0_r = w[:, 1] * DEG2RAD
    cd11, cd12, cd21, cd22 = w[:, 4], w[:, 5], w[:, 6], w[:, 7]
    cols = [
        accepts.astype(jnp.float32), w[:, 0], jnp.sin(dec0_r), jnp.cos(dec0_r),
        w[:, 2], w[:, 3], cd11, cd12, cd21, cd22, cd11 * cd22 - cd12 * cd21,
    ]
    return jnp.stack(cols, axis=1)


def _sky_to_pixel(gra, gdec, rec):
    """Gnomonic sky->pixel for a pixel row; ``rec(k)`` reads record scalar k.

    Mirrors `geometry.sky_to_tangent` + `sky_to_pixel` operation for
    operation (the RA offset is taken in degrees before the radian
    conversion, which keeps float32 from cancelling ~37-degree RAs).
    """
    ra0, sin_dec0, cos_dec0, x0, y0 = rec(1), rec(2), rec(3), rec(4), rec(5)
    cd11, cd12, cd21, cd22, det = rec(6), rec(7), rec(8), rec(9), rec(10)
    dra = (gra - ra0) * DEG2RAD
    dec_r = gdec * DEG2RAD
    sin_dec = jnp.sin(dec_r)
    cos_dec = jnp.cos(dec_r)
    cos_dra = jnp.cos(dra)
    cosc = sin_dec0 * sin_dec + cos_dec0 * cos_dec * cos_dra
    xi = cos_dec * jnp.sin(dra) / cosc * RAD2DEG
    eta = (cos_dec0 * sin_dec - sin_dec0 * cos_dec * cos_dra) / cosc * RAD2DEG
    sx = (cd22 * xi - cd12 * eta) / det + x0
    sy = (-cd21 * xi + cd11 * eta) / det + y0
    return sx, sy


def _bilinear_via_matmul(image, sx, sy):
    """Bilinear sample at a (1, n) pixel row: onehot-matmul row gather +
    masked column select.  Returns (masked values, inside mask), (1, n)."""
    h, w = image.shape
    n = sx.shape[1]
    x0f = jnp.floor(sx)
    y0f = jnp.floor(sy)
    dx = sx - x0f
    dy = sy - y0f
    x0 = jnp.clip(x0f.astype(jnp.int32), 0, w - 1)
    x1 = jnp.clip(x0f.astype(jnp.int32) + 1, 0, w - 1)
    y0 = jnp.clip(y0f.astype(jnp.int32), 0, h - 1)
    y1 = jnp.clip(y0f.astype(jnp.int32) + 1, 0, h - 1)

    rows_iota = jax.lax.broadcasted_iota(jnp.int32, (h, n), 0)
    oh_y0 = (rows_iota == y0).astype(image.dtype)
    oh_y1 = (rows_iota == y1).astype(image.dtype)
    # MXU: image^T @ onehot -> (w, n); column p holds source row y[p].
    gather = functools.partial(
        jax.lax.dot_general,
        dimension_numbers=(((0,), (0,)), ((), ())),
        precision=_HIGHEST,
        preferred_element_type=jnp.float32,
    )
    rows0 = gather(image, oh_y0)
    rows1 = gather(image, oh_y1)

    cols_iota = jax.lax.broadcasted_iota(jnp.int32, (w, n), 0)
    oh_x0 = (cols_iota == x0).astype(image.dtype)
    oh_x1 = (cols_iota == x1).astype(image.dtype)
    v00 = jnp.sum(rows0 * oh_x0, axis=0, keepdims=True)
    v01 = jnp.sum(rows0 * oh_x1, axis=0, keepdims=True)
    v10 = jnp.sum(rows1 * oh_x0, axis=0, keepdims=True)
    v11 = jnp.sum(rows1 * oh_x1, axis=0, keepdims=True)

    val = (
        v00 * (1 - dx) * (1 - dy)
        + v01 * dx * (1 - dy)
        + v10 * (1 - dx) * dy
        + v11 * dx * dy
    )
    inside = (sx >= 0) & (sx <= w - 1) & (sy >= 0) & (sy <= h - 1)
    # A select, not val * mask: an empty pack slot (all-zero WCS, det 0)
    # projects to NaN coordinates, and NaN * 0 is NaN on the TPU.
    return jnp.where(inside, val, 0.0), inside.astype(image.dtype)


def _conv_band_matrix(tap, k_width: int, n: int, dtype):
    """(n, n) banded matrix M with M @ x == edge-padded 1-D conv of x.

    M[i, j] = sum_m tap(m) * [j == clip(i + m - r, 0, n-1)] — identical to
    ``jnp.convolve(pad(x, edge), kernel, 'valid')`` for the symmetric
    (Gaussian) kernels `matching_kernel_bank` emits.  Built from iotas and a
    static loop over the K taps (scalars read from SMEM by ``tap``), so the
    separable PSF convolution becomes two matmuls — the same dense-algebra
    reformulation as the row gather (§2).
    """
    r = (k_width - 1) // 2
    rows = jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)
    m_mat = jnp.zeros((n, n), dtype)
    for m in range(k_width):
        src = jnp.clip(rows + (m - r), 0, n - 1)
        m_mat = m_mat + tap(m) * (cols == src).astype(dtype)
    return m_mat


def _dot(a, b):
    return jnp.dot(a, b, precision=_HIGHEST, preferred_element_type=jnp.float32)


def _dot_nt(a, b):
    """a @ b.T without materializing the transpose."""
    return jax.lax.dot_general(
        a, b, (((1,), (1,)), ((), ())),
        precision=_HIGHEST, preferred_element_type=jnp.float32,
    )


def _convolve_sep_matmul(image, tap, k_width: int):
    """Separable PSF convolution as two MXU matmuls (edge-padded)."""
    if k_width == 1:
        return image * tap(0)
    h, w = image.shape
    m_h = _conv_band_matrix(tap, k_width, h, image.dtype)
    m_w = _conv_band_matrix(tap, k_width, w, image.dtype)
    return _dot(m_h, _dot_nt(image, m_w))   # rows, then cols


def _convolve_2d_matmul(image, tap, kh: int, kw: int):
    """Non-separable 2-D PSF correlation as Kh banded-matmul pairs.

    The measured-PSF homogenization kernels (`psf.homogenization_bank`) are
    full (Kh, Kw) tap grids — no separable factorization exists for an
    elliptical Moffat matching kernel.  Decompose by kernel *row* instead:

      out = sum_m  S_m @ (image @ W_m.T)

    where W_m is the banded matrix applying kernel row m along the width
    axis (`_conv_band_matrix` — a correlation with edge clamp) and S_m is
    the one-band row-shift selection ``S_m[i, j] = [j == clip(i+m-rh)]``.
    Both factors are iota-built dense matrices, so the whole convolution is
    2*Kh MXU matmuls.  Semantics match `psf.convolve_2d` exactly:
    edge-clamped cross-correlation.  ``tap(m, c)`` reads tap (m, c).
    """
    if kh == 1 and kw == 1:
        return image * tap(0, 0)
    h, w = image.shape
    rh = (kh - 1) // 2
    rows = jax.lax.broadcasted_iota(jnp.int32, (h, h), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (h, h), 1)
    out = jnp.zeros_like(image)
    for m in range(kh):
        w_m = _conv_band_matrix(lambda c: tap(m, c), kw, w, image.dtype)
        row_sel = (cols == jnp.clip(rows + (m - rh), 0, h - 1)).astype(
            image.dtype
        )
        out = out + _dot(row_sel, _dot_nt(image, w_m))
    return out


def _accumulate(pairs):
    """First image step writes each output block, later image steps add."""
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _init():
        for ref, v in pairs:
            ref[...] = v

    @pl.when(i > 0)
    def _accum():
        for ref, v in pairs:
            ref[...] += v


# ----- shared operand plumbing for every fused warp+reduce kernel -----

def _fused_inputs(pixels, wcs_vecs, accepts, grid_ra, grid_dec, psf_kernels,
                  block_rows):
    """Grid + specs + operand prefix shared by every fused coadd kernel.

    Returns (grid, in_specs, operands, taps, q, bn): ``taps`` is the static
    per-image PSF tap shape — () without matching, (K,) separable,
    (Kh, Kw) measured 2-D — and bn the output pixels per grid step.
    Callers append their pass-specific (1, Q*Q) operands after the grids.
    """
    n, h, w = pixels.shape
    q = grid_ra.shape[0]
    bn = _pixel_block(q, min(block_rows, q))
    in_specs = [
        _SMEM,
        pl.BlockSpec((1, h, w), lambda r, i: (i, 0, 0)),
        _row_spec(bn),
        _row_spec(bn),
    ]
    operands = [
        _image_records(wcs_vecs, accepts),
        pixels.astype(jnp.float32),
        _as_row(grid_ra),
        _as_row(grid_dec),
    ]
    taps = ()
    if psf_kernels is not None:
        taps = tuple(psf_kernels.shape[1:])
        in_specs.insert(1, _SMEM)
        operands.insert(1, psf_kernels.astype(jnp.float32).reshape(n, -1))
    return (q * q // bn, n), in_specs, operands, taps, q, bn


def _warped_sample(refs, taps):
    """Shared per-step prologue: unpack refs, PSF-prep, warp one image.

    ``refs`` is the operand-ref prefix [records, (taps?), image, gra, gdec];
    returns (accept scalar, masked value, mask, leftover refs).
    """
    i = pl.program_id(1)
    rec_ref = refs[0]
    if taps:
        kern_ref, image_ref, gra_ref, gdec_ref = refs[1:5]
        rest = refs[5:]
        if len(taps) == 2:
            kw = taps[1]
            img = _convolve_2d_matmul(
                image_ref[0], lambda m, c: kern_ref[i, m * kw + c], *taps
            )
        else:
            img = _convolve_sep_matmul(
                image_ref[0], lambda m: kern_ref[i, m], taps[0]
            )
    else:
        image_ref, gra_ref, gdec_ref = refs[1:4]
        rest = refs[4:]
        img = image_ref[0]
    sx, sy = _sky_to_pixel(
        gra_ref[...], gdec_ref[...], lambda k: rec_ref[i, k]
    )
    vm, m = _bilinear_via_matmul(img, sx, sy)
    return rec_ref[i, 0], vm, m, rest


def _coadd_fused_kernel(*refs, taps):
    """Algorithm 1: coadd += accepted warped values, depth += coverage."""
    a, vm, m, (coadd_ref, depth_ref) = _warped_sample(refs, taps)
    _accumulate([(coadd_ref, vm * a), (depth_ref, m * a)])


def _coadd_moments_kernel(*refs, taps):
    """Robust pass 1: weighted moments S0 = Σc, S1 = Σt, S2 = Σt²/c."""
    a, vm, m, (s0_ref, s1_ref, s2_ref) = _warped_sample(refs, taps)
    # vm is already mask-scaled; t²/c with binary per-pixel coverage is
    # vm²/m, guarded where the image does not cover the pixel.
    s2c = jnp.where(m > 0, vm * vm / jnp.where(m > 0, m, 1.0), 0.0)
    _accumulate([(s0_ref, m * a), (s1_ref, vm * a), (s2_ref, s2c * a)])


def _coadd_clip_kernel(*refs, taps):
    """Robust final pass: accumulate only samples inside |x - center| <= r.

    ``center``/``thresh`` are fixed per-pixel operands from the completed
    moments (or histogram) pass, blocked identically to the output rows.
    """
    a, vm, m, rest = _warped_sample(refs, taps)
    center_ref, thresh_ref, coadd_ref, depth_ref = rest
    # Division-free form, matching reducer.clip_local bit-for-bit:
    # |vm - m*center| <= m*thresh  ==  |vm/m - center| <= thresh for m > 0.
    keep = ((m > 0)
            & (jnp.abs(vm - m * center_ref[...]) <= m * thresh_ref[...])
            ).astype(vm.dtype)
    _accumulate([(coadd_ref, vm * keep * a), (depth_ref, m * keep * a)])


def _coadd_hist_kernel(*refs, taps, nbins):
    """Median round 1: coverage-weighted binapprox histogram.

    Output block is (nbins, pixels) — every step owns the full bin axis of
    its pixel block; the bin scatter is one dense compare against a bin
    iota along the sublanes (no TPU gather needed).
    """
    a, vm, m, rest = _warped_sample(refs, taps)
    lo_ref, inv_w_ref, hist_ref = rest
    x = jnp.where(m > 0, vm / jnp.where(m > 0, m, 1.0), 0.0)
    b = jnp.clip(jnp.floor((x - lo_ref[...]) * inv_w_ref[...]), 0, nbins - 1)
    wgt = m * a
    bins = jax.lax.broadcasted_iota(jnp.int32, hist_ref.shape, 0)
    _accumulate([(hist_ref, jnp.where(bins.astype(b.dtype) == b, wgt, 0.0))])


def _fused_call(kernel, inputs, extra_rows, n_out, interpret):
    """pallas_call of a fused kernel with ``n_out`` (1, Q*Q) row outputs."""
    grid, in_specs, operands, taps, q, bn = inputs
    in_specs = in_specs + [_row_spec(bn)] * len(extra_rows)
    operands = operands + [_as_row(a) for a in extra_rows]
    out = pl.pallas_call(
        functools.partial(kernel, taps=taps),
        grid=grid,
        in_specs=in_specs,
        out_specs=[_row_spec(bn)] * n_out,
        out_shape=[jax.ShapeDtypeStruct((1, q * q), jnp.float32)] * n_out,
        # Pixel blocks are independent; images accumulate sequentially.
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(*operands)
    return tuple(o.reshape(q, q) for o in out)


def coadd_fused(
    pixels: jnp.ndarray,    # (N, H, W)
    wcs_vecs: jnp.ndarray,  # (N, 8)
    accepts: jnp.ndarray,   # (N,)
    grid_ra: jnp.ndarray,   # (Q, Q)
    grid_dec: jnp.ndarray,  # (Q, Q)
    *,
    psf_kernels: jnp.ndarray | None = None,  # (N, K) rows or (N, K, K) taps
    block_rows: int,
    interpret: bool,
):
    """Algorithm 1 in one kernel: projected tiles never touch HBM."""
    inputs = _fused_inputs(pixels, wcs_vecs, accepts, grid_ra, grid_dec,
                           psf_kernels, block_rows)
    return _fused_call(_coadd_fused_kernel, inputs, [], 2, interpret)


def coadd_moments(
    pixels: jnp.ndarray,    # (N, H, W)
    wcs_vecs: jnp.ndarray,  # (N, 8)
    accepts: jnp.ndarray,   # (N,)
    grid_ra: jnp.ndarray,   # (Q, Q)
    grid_dec: jnp.ndarray,  # (Q, Q)
    *,
    psf_kernels: jnp.ndarray | None = None,
    block_rows: int,
    interpret: bool,
):
    """Fused robust pass 1 -> (S0, S1, S2) moment maps, one kernel."""
    inputs = _fused_inputs(pixels, wcs_vecs, accepts, grid_ra, grid_dec,
                           psf_kernels, block_rows)
    return _fused_call(_coadd_moments_kernel, inputs, [], 3, interpret)


def coadd_clip(
    pixels: jnp.ndarray,
    wcs_vecs: jnp.ndarray,
    accepts: jnp.ndarray,
    grid_ra: jnp.ndarray,
    grid_dec: jnp.ndarray,
    center: jnp.ndarray,    # (Q, Q) clip center (mean or binapprox median)
    thresh: jnp.ndarray,    # (Q, Q) clip radius
    *,
    psf_kernels: jnp.ndarray | None = None,
    block_rows: int,
    interpret: bool,
):
    """Fused robust final pass -> (coadd, depth) of surviving samples."""
    inputs = _fused_inputs(pixels, wcs_vecs, accepts, grid_ra, grid_dec,
                           psf_kernels, block_rows)
    return _fused_call(_coadd_clip_kernel, inputs, [center, thresh], 2,
                       interpret)


def coadd_hist(
    pixels: jnp.ndarray,
    wcs_vecs: jnp.ndarray,
    accepts: jnp.ndarray,
    grid_ra: jnp.ndarray,
    grid_dec: jnp.ndarray,
    lo: jnp.ndarray,        # (Q, Q) binapprox lower bound (mu - sigma)
    inv_w: jnp.ndarray,     # (Q, Q) reciprocal bin width
    *,
    nbins: int = 16,
    psf_kernels: jnp.ndarray | None = None,
    block_rows: int,
    interpret: bool,
):
    """Fused median round 1 -> (nbins, Q, Q) weighted binapprox histogram."""
    grid, in_specs, operands, taps, q, bn = _fused_inputs(
        pixels, wcs_vecs, accepts, grid_ra, grid_dec, psf_kernels, block_rows
    )
    out = pl.pallas_call(
        functools.partial(_coadd_hist_kernel, taps=taps, nbins=nbins),
        grid=grid,
        in_specs=in_specs + [_row_spec(bn)] * 2,
        out_specs=pl.BlockSpec((nbins, bn), lambda r, i: (0, r)),
        out_shape=jax.ShapeDtypeStruct((nbins, q * q), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(*operands, _as_row(lo), _as_row(inv_w))
    return out.reshape(nbins, q, q)


# ----- brick mosaic: place cached tiles into a query canvas (§9) -----
def _mosaic_kernel(off_ref, tile_ref, cov_ref, coadd_ref, depth_ref, *,
                   bh, bw, npix):
    """One grid step merges one brick tile at its dynamic (row, col) offset.

    The outputs map the full canvas on every step (constant index_map), so
    the canvas accumulates across grid steps: zeroed on the first step,
    then each tile is *placed* by two one-hot matmuls — P_r (npix, bh) and
    P_c (bw, npix) select the tile's rows and columns — rather than a
    dynamic slice, which Mosaic cannot offset along the lane axis.  At
    HIGHEST precision a one-hot product reproduces every tile value
    exactly, so the merge stays the same reduce monoid as the XLA
    `reducer.mosaic_tiles`, bit for bit.
    """
    b = pl.program_id(0)

    @pl.when(b == 0)
    def _init():
        coadd_ref[...] = jnp.zeros_like(coadd_ref)
        depth_ref[...] = jnp.zeros_like(depth_ref)

    r = off_ref[b, 0]
    c = off_ref[b, 1]
    p_rows = (
        jax.lax.broadcasted_iota(jnp.int32, (npix, bh), 0)
        == jax.lax.broadcasted_iota(jnp.int32, (npix, bh), 1) + r
    ).astype(jnp.float32)
    p_cols = (
        jax.lax.broadcasted_iota(jnp.int32, (bw, npix), 1)
        == jax.lax.broadcasted_iota(jnp.int32, (bw, npix), 0) + c
    ).astype(jnp.float32)
    coadd_ref[...] += _dot(p_rows, _dot(tile_ref[0], p_cols))
    depth_ref[...] += _dot(p_rows, _dot(cov_ref[0], p_cols))


def mosaic_bricks(
    tiles: jnp.ndarray,    # (B, bh, bw) cached brick coadds
    covs: jnp.ndarray,     # (B, bh, bw) weight (depth) maps
    offsets: jnp.ndarray,  # (B, 2) int32 (row, col) canvas positions
    npix: int,
    *,
    interpret: bool,
):
    """(npix, npix) coadd + depth mosaicked from cached brick tiles."""
    n, bh, bw = tiles.shape
    out = pl.pallas_call(
        functools.partial(_mosaic_kernel, bh=bh, bw=bw, npix=npix),
        grid=(n,),
        in_specs=[
            _SMEM,
            pl.BlockSpec((1, bh, bw), lambda b: (b, 0, 0)),
            pl.BlockSpec((1, bh, bw), lambda b: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((npix, npix), lambda b: (0, 0)),
            pl.BlockSpec((npix, npix), lambda b: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((npix, npix), jnp.float32),
            jax.ShapeDtypeStruct((npix, npix), jnp.float32),
        ],
        # Tiles accumulate into one canvas: the single grid dim is sequential.
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(offsets.astype(jnp.int32), tiles.astype(jnp.float32),
      covs.astype(jnp.float32))
    return out[0], out[1]
