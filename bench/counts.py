"""Operations and bytes a coadd query needs, whatever implements it.

The work is counted from the answer, not from the program: ``samples`` is
the sum of the output's depth (every frame pixel sampled onto the output
grid), which the correctness comparison holds to the reference.  Both
counts are lower bounds, so a roofline share built on them cannot pass 100%
however a later change reorganizes the scan.
"""

from __future__ import annotations

# Arithmetic of one sample, written out.  Per-frame and per-output-pixel
# constants (sines of the frame centre, the grid's sky coordinates) are not
# counted; transcendental functions count as one operation each.
OPS_PER_SAMPLE = {
    # TAN sky -> tangent plane: dra (sub, scale), sin and cos of dra,
    # cos c (3 mul, 1 add), xi (mul, div), eta (3 mul, sub, div),
    # two scalings to degrees: 17.
    "tan_projection": 2 + 2 + 4 + 2 + 5 + 2,
    # Tangent plane -> pixel through the inverse CD matrix: 2 x (2 mul,
    # 1 sub, 1 mul, 1 add).
    "cd_inverse": 10,
    # Bilinear: 2 floor, 2 fractions, 4 bound compares, 2 complements,
    # 4 weight products, 4 tap products and 3 adds.
    "bilinear": 2 + 2 + 4 + 2 + 4 + 7,
    # Stack: coadd and depth accumulation.
    "accumulate": 2,
}
OPS = sum(OPS_PER_SAMPLE.values())
# Each sample reads its frame pixels at least once at native scale or
# coarser (4 bytes of float32 per sample); the output writes coadd and depth.
BYTES_PER_SAMPLE = 4
BYTES_PER_OUTPUT_PIXEL = 8


def query_work(samples: float, npix: int):
    """(operations, bytes) the query with ``samples`` samples needs."""
    return OPS * samples, BYTES_PER_SAMPLE * samples + BYTES_PER_OUTPUT_PIXEL * npix * npix


def roofline_seconds(ops: float, nbytes: float, peaks: dict):
    """(least seconds, the bound that sets it: "compute" or "bytes")."""
    t_ops = ops / peaks["flops_per_s"]
    t_bytes = nbytes / peaks["hbm_bytes_per_s"]
    return (t_ops, "compute") if t_ops >= t_bytes else (t_bytes, "bytes")
