"""CoaddEngine: the paper's MapReduce coaddition job, end to end.

Implements all six input-format strategies of Table 1 / Table 2 so the
benchmarks can reproduce the paper's comparisons measurably:

  1. ``raw_fits``                 — per-file dispatch, no prefilter (the
                                    paper only estimated this row; we measure)
  2. ``raw_fits_prefiltered``     — glob (band x camcol) prefilter, then
                                    per-file dispatch            (§4.1.1)
  3. ``unstructured_seq``         — packed containers, random layout; no
                                    pruning possible; all packs read (§4.1.2)
  4. ``structured_seq_prefiltered``— containers keyed by (band, camcol);
                                    container-level glob pruning (§4.1.3)
  5. ``sql_unstructured``         — exact spatial-index selection gathered
                                    from the unstructured containers (§4.1.4)
  6. ``sql_structured``           — exact selection gathered from structured
                                    containers (better locality -> fewer
                                    containers touched)          (§4.1.4)

Plan/execute split (DESIGN.md §4): each method is a pure *planner*
(``plan_<method>(query) -> CoaddPlan``: layout + (P, cap) slot gate + query
vector + locate stats — the paper's job-init phase) feeding one of three
*executors* over resident data:

* ``execute(plan)``          — one jitted `lax.scan` over the device-resident
                               layout (PR 1's one-dispatch path).
* ``run_batch(queries, m)``  — stacks same-layout plans and vmaps the scan
                               over the query axis: K queries, ONE dispatch
                               (the paper's Fig. 5 multi-query amortization).
* ``run_distributed(...)``   — the production path: the structured layout is
                               sharded onto the mesh **once**
                               (`MeshResidentDataset`, cached per
                               (layout, mesh)); each job ships only slot
                               gates + query vectors + grids, maps locally
                               under `shard_map`, and reduces by psum +
                               reduce-scatter (see `reducer.py`).

When ``match_psf_sigma`` is set, the map stage first convolves every image
to that common PSF width using a host-precomputed per-slot kernel bank —
measured-PSF homogenization kernels (`psf.homogenization_bank`, Fourier
least squares over the survey's empirical stamps) when the layout carries
stamps, the separable Gaussian bank (`psf.matching_kernel_bank` over
``psf_sigma``) otherwise — threaded as a plain operand through the XLA
mapper, the Pallas ``coadd_fused`` kernel (1-D banded or 2-D banded-matmul
variants), and the distributed mesh job.  On the XLA path the matching
convolution is query-independent, so by default it runs ONCE per
(layout, target) at residency time and the *matched pixels* are cached
under the device budget (`matched_pixel_cache`, DESIGN.md §7); the Pallas
path keeps the documented in-kernel recompute instead (fusion trades MXU
for HBM).

Sparse execution (DESIGN.md §5, default on): the planner's gate also sets
the *scan extent*.  Each executor gathers just the packs the gate opens out
of the resident arrays (``jnp.take`` over a budget-bucketed pack-index
vector) and scans the compacted result, so map cost tracks ``packs_gated``
rather than the layout size; the degenerate per-file layout is additionally
reblocked into dense super-packs at residency time.  ``sparse=False``
restores the dense masked-discard scan over every pack.
"""

from __future__ import annotations

import dataclasses
import hashlib
import os
import time
from collections import OrderedDict
from functools import partial
from typing import Dict, FrozenSet, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import mapper, psf, reducer
from repro.core.bricks import BrickCover, BrickGrid
from repro.core.durable import BrickSpill, JournalStore
from repro.core.faults import ChaosInjector, PoisonedChunkError
from repro.core.jobtracker import (
    BrickTask,
    FaultCounters,
    MaterializeReport,
    MaterializeTracker,
    WindowTracker,
)
from repro.core.plan import (
    CoaddPlan,
    ScanWindow,
    SparseScanIndex,
    compact_gate,
    compact_gates,
    compact_window_gate,
    compact_window_gates,
    grid_digest,
    sparse_pack_index,
    stack_plans,
    union_sparse_index,
    window_schedule,
)
from repro.core.prefilter import (
    SpatialIndex,
    camcol_dec_table,
    glob_file_mask,
    glob_pack_mask,
)
from repro.core.query import CoaddQuery
from repro.core.seqfile import (
    COST_MATCHED_CHUNK,
    COST_RAW_CHUNK,
    BrickMeta,
    BrickStore,
    DevicePackedDataset,
    MeshResidentDataset,
    PackedDataset,
    ResidencyManager,
    SlotRemap,
    pack_per_file,
    pack_structured,
    pack_unstructured,
)
from repro.core.spans import span
from repro.core.survey import Survey
from repro.distributed.sharding import (
    shard_count,
    shard_local_compaction,
)
from repro.kernels.warp import ops as warp_ops
from repro.kernels.warp import windowed

METHODS = (
    "raw_fits",
    "raw_fits_prefiltered",
    "unstructured_seq",
    "structured_seq_prefiltered",
    "sql_unstructured",
    "sql_structured",
)


@dataclasses.dataclass
class JobStats:
    method: str
    files_considered: int          # mapper input records (Table 2)
    files_contributing: int        # actual coverage
    packs_touched: int             # "mapper objects" locality proxy (§4.1.4):
                                   #   distinct planning-layout containers the
                                   #   gate opens; `run_distributed` reports
                                   #   mesh shard slabs touched by the flat
                                   #   gate (pack identity is lost there)
    t_locate_s: float              # job-init: prefilter/index/gather ("RPC")
    t_map_reduce_s: float          # device compute
    t_total_s: float
    dispatches: int = 1            # jitted device dispatches for this query
    # Sparse-execution accounting (DESIGN.md §5) — gated vs scanned work:
    packs_gated: int = 0           # execution-layout packs the gate opens
    packs_scanned: int = 0         # pack-axis scan steps actually executed;
                                   #   additive: batched/distributed jobs
                                   #   attribute the job's scan work to the
                                   #   first result (like dispatches), and
                                   #   run_distributed counts all shards
                                   #   (n_shards * scan_budget)
    scan_budget: int = 0           # static per-program bucket the scan
                                   #   compiled for (n_packs if dense; the
                                   #   per-shard budget in run_distributed);
                                   #   descriptive, not additive — every
                                   #   result in a job reports it
    # Streaming-residency accounting (DESIGN.md §6).  Zero on the eager
    # path (no device budget configured); attribution follows the same
    # rules as above — windows is descriptive, chunk counters are additive
    # (batched/distributed jobs put them on the first result).
    windows: int = 0               # residency windows the query scanned
    chunk_uploads: int = 0         # chunks uploaded during this call (misses)
    residency_hits: int = 0        # chunks served already-resident
    residency_evictions: int = 0   # LRU evictions this call forced
    # Matched-pixel cache accounting (DESIGN.md §7) — device-side PSF
    # convolutions this call built vs reused; zero when matching is off,
    # the Pallas in-kernel path runs, or the cache is disabled.
    matched_cache_builds: int = 0  # (layout, target) matched arrays built
    matched_cache_hits: int = 0    # matched arrays served already-resident
    # True residency high-water mark — the honest version of the advisory
    # budget accounting; descriptive, not additive.  Streaming: budget +
    # one in-flight window's operands, matched-pixel cache included.
    # Eager: also counts the unmanaged whole-layout uploads and device
    # banks, so matched mode reports raw + matched copies both resident.
    peak_resident_bytes: int = 0
    # Fault-domain accounting (DESIGN.md §8) — what the WindowTracker did
    # to finish this query.  Counters are additive (batched jobs put them
    # on the first result); ``partial``/``uncovered_packs`` are
    # descriptive and reported on every result of a job.  All zero/False
    # on the eager path and on clean tracked runs.
    retries: int = 0               # failed attempts that were re-executed
    speculative_windows: int = 0   # straggler backups launched (digest-verified)
    quarantined_packs: int = 0     # packs gated out after persistent poison
    resumed_windows: int = 0       # journal hits replayed instead of re-run
    partial: bool = False          # True when quarantine removed coverage
    uncovered_packs: Tuple[int, ...] = ()  # exec-layout packs quarantined out
    requarantine_released: int = 0 # packs restored by digest re-verification
                                   #   (`reverify_quarantined`) since the
                                   #   previous streaming result; additive
    # Brick-serving accounting (DESIGN.md §9) — how `run(use_bricks=True)`
    # covered this query.  All additive (a mosaic is one result); zero on
    # every brick-free path.  ``bricks_hit`` counts tiles served from the
    # device tier, ``bricks_spilled`` tiles re-uploaded from the host tier
    # after LRU pressure dropped their device replica, ``bricks_missed``
    # tiles that had to be freshly materialized inline, and
    # ``residual_packs_scanned`` the streaming scan work those misses paid
    # (the warm path's number is 0 — that gap is the whole point).
    bricks_hit: int = 0
    bricks_missed: int = 0
    bricks_spilled: int = 0
    residual_packs_scanned: int = 0
    # Robust-reduction accounting (DESIGN.md §11): which reduction variant
    # produced this result ("mean" | "clipped" | "median") and how many
    # monoidal passes over the windows it took (1 on the mean path and on
    # every eager path — the fused program re-scans internally).
    reduce: str = "mean"
    reduce_passes: int = 1
    # Transfer accounting, from array metadata (no sync): bytes of the
    # per-query operands this call copied to the device (output grid, slot
    # gate, pack index, query vector; a layout's one-time upload is not
    # counted) and bytes it read back (scalars, coadd, depth).  The same
    # numbers are the ``h2d_bytes``/``d2h_bytes`` arguments of the
    # ``coadd.execute.*`` spans.  Counted on the eager resident `execute`;
    # zero on the streaming, batch, brick and distributed paths.
    h2d_bytes: int = 0
    d2h_bytes: int = 0
    # Pack-axis scan steps that warped through the windowed kernel
    # (`kernels.warp.windowed`), as the device counts them; the rest of
    # ``packs_scanned`` took the XLA gather.  Counted on the eager resident,
    # streaming and batch mean paths (the ones that can take it).  The
    # ``coadd.execute.dispatch`` span's ``windowed_packs`` argument is the
    # pack steps handed to the windowed program; the two differ only when
    # the kernel's guard voided the answer and the query was redone
    # through the gather (`_covered`), which counts 0 here.
    windowed_packs: int = 0


@dataclasses.dataclass
class CoaddResult:
    coadd: np.ndarray
    depth: np.ndarray
    stats: JobStats

    @property
    def normalized(self) -> np.ndarray:
        # Exact masking, no epsilon clamp: robust clip masks make fractional
        # depths (a 0.5-coverage border pixel) routine, and max(depth, 1e-6)
        # would rescale them instead of dividing by the true weight.
        return np.where(
            self.depth > 0, self.coadd / np.where(self.depth > 0, self.depth, 1.0), 0.0
        )


def _nbytes(*arrays) -> int:
    """Summed size of the arrays (None counts 0), from metadata: no sync."""
    return sum(int(a.nbytes) for a in arrays if a is not None)


def _query_vec(query: CoaddQuery) -> np.ndarray:
    t0, t1 = query.time_window()
    # Large-but-finite sentinels keep the vector finite for jit friendliness.
    t0 = max(t0, -1e30)
    t1 = min(t1, 1e30)
    return np.array(
        [
            float(query.band_id),
            query.ra_bounds[0],
            query.ra_bounds[1],
            query.dec_bounds[0],
            query.dec_bounds[1],
            t0,
            t1,
        ],
        np.float32,
    )


def _covered(steps) -> bool:
    """Whether a mean scan's answer stands: no pack step's guard found a
    tap outside its windows (`kernels.warp.windowed`).  ``steps`` is the
    scan's fifth result, [pack steps warped, pack steps voided] (per query
    in a batch; zeros where the gather ran).  A voided answer is redone
    through the gather."""
    return not np.asarray(steps)[..., 1].any()


def _accept_from_meta(ints, floats, qvec):
    band_ok = ints["band_id"].astype(jnp.float32) == qvec[0]
    valid = ints["image_id"] >= 0
    ra_ok = (floats["ra_max"] >= qvec[1]) & (floats["ra_min"] <= qvec[2])
    dec_ok = (floats["dec_max"] >= qvec[3]) & (floats["dec_min"] <= qvec[4])
    t_ok = (floats["t_obs"] >= qvec[5]) & (floats["t_obs"] <= qvec[6])
    return band_ok & valid & ra_ok & dec_ok & t_ok


def _map_reduce(pixels, wcs, accept, grid_ra, grid_dec, psf_kernels,
                use_kernel, block_rows=None):
    """Map + local reduce of one (N, H, W) batch -> (coadd, depth).

    The Pallas lane fuses both stages in `coadd_fused` (the projected tiles
    never leave VMEM); the XLA lane projects with `mapper.map_batch` and
    sums the tile stack.
    """
    if use_kernel:
        return warp_ops.coadd_fused(
            pixels, wcs, accept.astype(jnp.float32), grid_ra, grid_dec,
            psf_kernels=psf_kernels, block_rows=block_rows,
        )
    tiles, covs = mapper.map_batch(
        pixels, wcs, accept, grid_ra, grid_dec, psf_kernels=psf_kernels
    )
    return reducer.reduce_local(tiles, covs)


@partial(jax.jit, static_argnames=("use_kernel",))
def _coadd_batch(pixels, wcs, ints, floats, qvec, grid_ra, grid_dec, use_kernel=False):
    """Map+local-reduce one dense batch of images. The jitted inner job."""
    accept = _accept_from_meta(ints, floats, qvec)
    coadd, depth = _map_reduce(
        pixels, wcs, accept, grid_ra, grid_dec, None, use_kernel
    )
    return coadd, depth, accept.sum()


def _scan_coadd(
    pixels,       # (P, cap, H, W) device-resident
    wcs,          # (P, cap, 8)
    ints,         # dict of (P, cap) int32
    floats,       # dict of (P, cap) float32
    psf_kernels,  # (P, cap, K) float32 matching-kernel bank, or None
    gate,         # (P, cap) bool — static shape, dynamic values
    qvec,         # (7,)
    grid_ra,      # (Q, Q)
    grid_dec,     # (Q, Q)
    use_kernel,
    block_rows,
    pack_idx=None,  # (G,) int32 — sparse: scan only these packs of the layout
    window=None,    # WindowFit: warp each pack with `coadd_windowed`
):
    """The whole query in ONE XLA program: scan packs, fuse map+reduce.

    The scan carries (coadd, depth, contributing); each step gates a pack's
    slots by metadata acceptance AND the caller's slot gate, (optionally)
    PSF-matches the slots, projects, and accumulates locally — so the
    (N, Q, Q) tile stack never materializes across packs and the dispatch
    count is 1 regardless of n_packs.  Non-gated slots contribute exact
    zeros (masked SPMD discard, Fig. 6).  Counts come back as device
    scalars: no per-pack host syncs.

    Sparse mode (``pack_idx`` given, DESIGN.md §5): the scan iterates the
    budget-bucketed index vector instead of the pack axis, and each step
    *streams* its pack out of the resident arrays (`mapper.gather_packs`
    with a scalar index) — the gather rides inside the scan, so no
    (G, cap, H, W) compacted copy ever materializes next to the resident
    layout.  ``gate`` must then be the (G, cap) compacted gate.

    Windowed mode (``window`` given, `CoaddEngine._window_fit`): each step
    hands the pack's index to `coadd_windowed`, which reads only each
    output tile's source window out of the resident pixels, in place of the
    XLA lane's four whole-pack gathers.  The fifth result is the int32
    pair [steps whose windows held every tap, steps whose guard found one
    outside] (zeros without ``window``): any of the second voids the
    answer, and the engine redoes the query through the gather
    (`_covered`).
    """

    def body(carry, px, wv, ints_p, floats_p, kern_p, gate_p):
        coadd, depth, contrib, steps = carry
        accept = _accept_from_meta(ints_p, floats_p, qvec) & gate_p
        if window is not None:
            c, d, covered = warp_ops.coadd_windowed(
                pixels, px, wv, accept, grid_ra, grid_dec, fit=window)
            steps = steps + jnp.stack([covered, 1 - covered])
        else:
            c, d = _map_reduce(px, wv, accept, grid_ra, grid_dec, kern_p,
                               use_kernel, block_rows)
        return (coadd + c, depth + d, contrib + accept.sum(), steps), None

    q = grid_ra.shape[0]
    init = (
        jnp.zeros((q, q), jnp.float32),
        jnp.zeros((q, q), jnp.float32),
        jnp.zeros((), jnp.int32),
        jnp.zeros((2,), jnp.int32),
    )
    (coadd, depth, contrib, steps), _ = _scan_packs(
        body, init, pixels, wcs, ints, floats, psf_kernels, gate, pack_idx,
        resident=window is not None,
    )
    return coadd, depth, contrib, gate.sum(), steps


def _scan_packs(body, init, pixels, wcs, ints, floats, psf_kernels, gate,
                pack_idx, resident=False):
    """Shared pack-scan plumbing: dense xs, or sparse streamed gather.

    ``body(carry, px, wv, ints_p, floats_p, kern_p, gate_p)`` is the per-pack
    monoid step; the dense/sparse split (DESIGN.md §5) lives here once so the
    mean scan and every robust pass (§11) iterate packs identically — which
    is what makes their per-pixel accumulation orders, and therefore the
    bitwise streaming/brick parity arguments, line up across reducers.

    Returns ``(carry, ys)``: bodies that emit per-pack outputs (the resident
    warp cache in `_robust_passes`) get them stacked along a leading pack
    axis; monoid-only bodies return None ys.

    ``resident=True`` hands the body the pack's index in place of its
    pixels, for bodies that read the resident array themselves.
    """
    if pack_idx is None:
        def step(carry, xs):
            px, wv, ints_p, floats_p, kern_p, gate_p = xs
            return body(carry, px, wv, ints_p, floats_p, kern_p, gate_p)

        first = jnp.arange(pixels.shape[0], dtype=jnp.int32) if resident else pixels
        xs = (first, wcs, ints, floats, psf_kernels, gate)
    else:
        def step(carry, xs):
            i, gate_p = xs
            px, wv, ints_p, floats_p, kern_p = mapper.gather_packs(
                i, pixels, wcs, ints, floats, psf_kernels
            )
            return body(carry, i if resident else px, wv, ints_p, floats_p,
                        kern_p, gate_p)

        xs = (pack_idx, gate)

    return jax.lax.scan(step, init, xs)


@partial(jax.jit, static_argnames=("use_kernel", "block_rows", "window"))
def _coadd_scan(
    pixels, wcs, ints, floats, psf_kernels, gate, qvec, grid_ra, grid_dec,
    use_kernel=False, block_rows=None, window=None,
):
    """One plan against a device-resident layout, as one jitted program."""
    return _scan_coadd(
        pixels, wcs, ints, floats, psf_kernels, gate, qvec, grid_ra, grid_dec,
        use_kernel, block_rows, window=window,
    )


def _over_queries(one, window, gates, qvecs, grids_ra, grids_dec):
    """Map a per-query scan over the stacked query axis: vmapped, or, in
    windowed mode, one query after another (`lax.map`), so each query's
    scan is the very program a solo query runs (the kernel's hand-written
    DMAs have no batching rule) and its answer does not depend on whether
    the service coalesced it."""
    if window is None:
        return jax.vmap(one)(gates, qvecs, grids_ra, grids_dec)
    return jax.lax.map(lambda xs: one(*xs), (gates, qvecs, grids_ra, grids_dec))


@partial(jax.jit, static_argnames=("use_kernel", "block_rows", "window"))
def _coadd_scan_batch(
    pixels, wcs, ints, floats, psf_kernels, gates, qvecs, grids_ra, grids_dec,
    use_kernel=False, block_rows=None, window=None,
):
    """K stacked plans against one resident layout, as ONE jitted program.

    vmaps the scan's gate/qvec/grid axes over the query dimension while the
    resident pack arrays broadcast — the batched multi-query job of paper
    Fig. 5 with zero extra pixel traffic (`_over_queries`).
    """

    def one(gate, qvec, grid_ra, grid_dec):
        return _scan_coadd(
            pixels, wcs, ints, floats, psf_kernels, gate, qvec, grid_ra,
            grid_dec, use_kernel, block_rows, window=window,
        )

    return _over_queries(one, window, gates, qvecs, grids_ra, grids_dec)


@partial(jax.jit, static_argnames=("use_kernel", "block_rows", "window"))
def _coadd_scan_sparse(
    pixels, wcs, ints, floats, psf_kernels, pack_idx, gate, qvec, grid_ra,
    grid_dec, use_kernel=False, block_rows=None, window=None,
):
    """Sparse plan against a resident layout, still ONE jitted program.

    The scan iterates the budget-bucketed (G,) index vector, streaming each
    gated pack out of the resident arrays per step — G scan steps instead of
    P, no compacted pixel copy.  ``gate`` arrives pre-compacted
    (`plan.compact_gate`), so padding rows are all-False and the
    considered/contributing counts match the dense scan exactly.
    """
    return _scan_coadd(
        pixels, wcs, ints, floats, psf_kernels, gate, qvec, grid_ra, grid_dec,
        use_kernel, block_rows, pack_idx=pack_idx, window=window,
    )


@partial(jax.jit, static_argnames=("use_kernel", "block_rows", "window"))
def _coadd_scan_batch_sparse(
    pixels, wcs, ints, floats, psf_kernels, pack_idx, gates, qvecs, grids_ra,
    grids_dec, use_kernel=False, block_rows=None, window=None,
):
    """K stacked plans over the union of their gated packs, ONE program.

    The gather set is the union across queries (`plan.union_sparse_index`);
    the vmapped per-query gates re-select each query's slots within it —
    preserving the K-queries-one-dispatch property while map work scales
    with the union's selectivity.  The index vector is shared (not vmapped):
    every query's scan streams the same G packs.
    """

    def one(gate, qvec, grid_ra, grid_dec):
        return _scan_coadd(
            pixels, wcs, ints, floats, psf_kernels, gate, qvec, grid_ra,
            grid_dec, use_kernel, block_rows, pack_idx=pack_idx,
            window=window,
        )

    return _over_queries(one, window, gates, qvecs, grids_ra, grids_dec)


# ----- robust reductions: monoidal pass programs (DESIGN.md §11) -----------
#
# Sigma-clipped and median stacks are not accumulate-only monoids, but they
# decompose into passes that are: moments (S0, S1, S2), an optional binapprox
# histogram, and a clip re-scan whose center/radius arrive as fixed operands.
# Each pass below is the same pack scan as `_scan_coadd` with a different
# per-pack monoid, so the streaming windows, journals, and brick tiles reuse
# every existing mechanism — they just run more passes.

def _scan_moments(
    pixels, wcs, ints, floats, psf_kernels, gate, qvec, grid_ra, grid_dec,
    use_kernel, block_rows, pack_idx=None,
):
    """Robust pass 1: coverage-weighted moments of the stack, ONE program."""

    def body(carry, px, wv, ints_p, floats_p, kern_p, gate_p):
        s0, s1, s2, contrib = carry
        accept = _accept_from_meta(ints_p, floats_p, qvec) & gate_p
        if use_kernel:
            a0, a1, a2 = warp_ops.coadd_moments(
                px, wv, accept.astype(jnp.float32), grid_ra, grid_dec,
                psf_kernels=kern_p, block_rows=block_rows,
            )
        else:
            tiles, covs = mapper.map_batch(
                px, wv, accept, grid_ra, grid_dec, psf_kernels=kern_p
            )
            a0, a1, a2 = reducer.moments_local(tiles, covs)
        return (s0 + a0, s1 + a1, s2 + a2, contrib + accept.sum()), None

    q = grid_ra.shape[0]
    z = jnp.zeros((q, q), jnp.float32)
    init = (z, z, z, jnp.zeros((), jnp.int32))
    (s0, s1, s2, contrib), _ = _scan_packs(
        body, init, pixels, wcs, ints, floats, psf_kernels, gate, pack_idx
    )
    return s0, s1, s2, contrib, gate.sum()


def _scan_hist(
    pixels, wcs, ints, floats, psf_kernels, gate, qvec, grid_ra, grid_dec,
    lo, inv_w, nbins, use_kernel, block_rows, pack_idx=None,
):
    """Median round 1: coverage-weighted binapprox histogram, ONE program."""

    def body(hist, px, wv, ints_p, floats_p, kern_p, gate_p):
        accept = _accept_from_meta(ints_p, floats_p, qvec) & gate_p
        if use_kernel:
            h = warp_ops.coadd_hist(
                px, wv, accept.astype(jnp.float32), grid_ra, grid_dec,
                lo, inv_w, nbins=nbins, psf_kernels=kern_p,
                block_rows=block_rows,
            )
        else:
            tiles, covs = mapper.map_batch(
                px, wv, accept, grid_ra, grid_dec, psf_kernels=kern_p
            )
            h = reducer.hist_local(tiles, covs, lo, inv_w, nbins)
        return hist + h, None

    q = grid_ra.shape[0]
    init = jnp.zeros((nbins, q, q), jnp.float32)
    return _scan_packs(
        body, init, pixels, wcs, ints, floats, psf_kernels, gate, pack_idx
    )[0]


def _scan_clip(
    pixels, wcs, ints, floats, psf_kernels, gate, qvec, grid_ra, grid_dec,
    center, thresh, use_kernel, block_rows, pack_idx=None,
):
    """Robust final pass: accumulate only samples inside the clip window."""

    def body(carry, px, wv, ints_p, floats_p, kern_p, gate_p):
        coadd, depth = carry
        accept = _accept_from_meta(ints_p, floats_p, qvec) & gate_p
        if use_kernel:
            c, d = warp_ops.coadd_clip(
                px, wv, accept.astype(jnp.float32), grid_ra, grid_dec,
                center, thresh, psf_kernels=kern_p,
                block_rows=block_rows,
            )
        else:
            tiles, covs = mapper.map_batch(
                px, wv, accept, grid_ra, grid_dec, psf_kernels=kern_p
            )
            c, d = reducer.clip_local(tiles, covs, center, thresh)
        return (coadd + c, depth + d), None

    q = grid_ra.shape[0]
    z = jnp.zeros((q, q), jnp.float32)
    return _scan_packs(
        body, (z, z), pixels, wcs, ints, floats, psf_kernels, gate, pack_idx
    )[0]


def _robust_passes(
    pixels, wcs, ints, floats, psf_kernels, gate, qvec, grid_ra, grid_dec,
    clip_k, use_kernel, block_rows, reduce, median_bins,
    pack_idx=None,
):
    """All robust passes composed in one traceable program (the eager path).

    Identical operand math to the streaming multi-pass contract — fusing
    only removes the host round-trips between passes, so the eager and
    streaming results agree to float tolerance (XLA may fuse the in-program
    center/threshold arithmetic differently from the between-pass jits).

    XLA path: the multi-pass schedule re-warps every sample per pass —
    mandatory for streaming windows, where the warped stack must never be
    resident, but a 2-3x warp tax when the layout already is.  So the eager
    XLA program warps each gated pack ONCE (the pack scan emits the warped
    (tiles, covs) as stacked scan outputs) and runs the whole estimator as
    `reducer.robust_local` over the stored stack: the clipped mean costs
    ~1 warp + cheap moments instead of 2 full warps.  The warped stack
    (n_packs*capacity, npix, npix) is resident for the dispatch — budget-
    bounded engines take the streaming multi-pass path instead, so this
    never competes with a device-memory budget.  The Pallas lane keeps the
    per-pass schedule: its fused warp+reduce kernels never materialize
    tiles, which is their point.
    """
    if not use_kernel:
        # Keep the warp body untouched (anything added to it — moment
        # partials in the carry or as extra scan outputs — measures
        # 20-30% slower end to end; XLA's scan codegen degrades once the
        # body grows reductions) and run the whole estimator over the
        # stored stack instead.
        def body(contrib, px, wv, ints_p, floats_p, kern_p, gate_p):
            accept = _accept_from_meta(ints_p, floats_p, qvec) & gate_p
            tiles, covs = mapper.map_batch(
                px, wv, accept, grid_ra, grid_dec, psf_kernels=kern_p
            )
            return contrib + accept.sum(), (tiles, covs)

        contrib, (tiles, covs) = _scan_packs(
            body, jnp.zeros((), jnp.int32), pixels, wcs, ints, floats,
            psf_kernels, gate, pack_idx,
        )
        q = grid_ra.shape[0]
        coadd, depth = reducer.robust_local(
            tiles.reshape(-1, q, q), covs.reshape(-1, q, q),
            reduce, clip_k, median_bins,
        )
        return coadd, depth, contrib, gate.sum()

    s0, s1, s2, contrib, considered = _scan_moments(
        pixels, wcs, ints, floats, psf_kernels, gate, qvec, grid_ra, grid_dec,
        use_kernel, block_rows, pack_idx=pack_idx,
    )
    mu, sigma = reducer.clip_stats(s0, s1, s2)
    if reduce == "median":
        lo, w, inv_w = reducer.hist_bounds(s0, s1, s2, median_bins)
        hist = _scan_hist(
            pixels, wcs, ints, floats, psf_kernels, gate, qvec, grid_ra,
            grid_dec, lo, inv_w, median_bins, use_kernel, block_rows,
            pack_idx=pack_idx,
        )
        center = reducer.hist_median(hist, s0, lo, w)
    else:
        center = mu
    thresh = reducer.clip_threshold(center, sigma, clip_k)
    coadd, depth = _scan_clip(
        pixels, wcs, ints, floats, psf_kernels, gate, qvec, grid_ra, grid_dec,
        center, thresh, use_kernel, block_rows, pack_idx=pack_idx,
    )
    return coadd, depth, contrib, considered


@partial(jax.jit, static_argnames=(
    "use_kernel", "block_rows", "reduce", "median_bins"))
def _robust_scan(
    pixels, wcs, ints, floats, psf_kernels, gate, qvec, grid_ra, grid_dec,
    clip_k, use_kernel=False, block_rows=None,
    reduce="clipped", median_bins=16, pack_idx=None,
):
    """One robust plan against a resident layout — still ONE dispatch."""
    return _robust_passes(
        pixels, wcs, ints, floats, psf_kernels, gate, qvec, grid_ra, grid_dec,
        clip_k, use_kernel, block_rows, reduce, median_bins,
        pack_idx=pack_idx,
    )


@partial(jax.jit, static_argnames=(
    "use_kernel", "block_rows", "reduce", "median_bins"))
def _robust_scan_batch(
    pixels, wcs, ints, floats, psf_kernels, gates, qvecs, grids_ra, grids_dec,
    clip_k, use_kernel=False, block_rows=None,
    reduce="clipped", median_bins=16, pack_idx=None,
):
    """K stacked robust plans, ONE dispatch (shared sparse index, like
    `_coadd_scan_batch_sparse`)."""

    def one(gate, qvec, grid_ra, grid_dec):
        return _robust_passes(
            pixels, wcs, ints, floats, psf_kernels, gate, qvec, grid_ra,
            grid_dec, clip_k, use_kernel, block_rows, reduce,
            median_bins, pack_idx=pack_idx,
        )

    return jax.vmap(one)(gates, qvecs, grids_ra, grids_dec)


# Streaming per-pass entry points: one jitted dispatch per (window, pass),
# returning additive partial tuples the WindowTracker can journal/resume.
@partial(jax.jit, static_argnames=("use_kernel", "block_rows"))
def _moments_scan_sparse(
    pixels, wcs, ints, floats, psf_kernels, pack_idx, gate, qvec,
    grid_ra, grid_dec, use_kernel=False, block_rows=None,
):
    return _scan_moments(
        pixels, wcs, ints, floats, psf_kernels, gate, qvec, grid_ra, grid_dec,
        use_kernel, block_rows, pack_idx=pack_idx,
    )


@partial(jax.jit, static_argnames=("use_kernel", "block_rows", "nbins"))
def _hist_scan_sparse(
    pixels, wcs, ints, floats, psf_kernels, pack_idx, gate, qvec,
    grid_ra, grid_dec, lo, inv_w, nbins=16, use_kernel=False, block_rows=None,
):
    return (_scan_hist(
        pixels, wcs, ints, floats, psf_kernels, gate, qvec, grid_ra, grid_dec,
        lo, inv_w, nbins, use_kernel, block_rows,
        pack_idx=pack_idx,
    ),)


@partial(jax.jit, static_argnames=("use_kernel", "block_rows"))
def _clip_scan_sparse(
    pixels, wcs, ints, floats, psf_kernels, pack_idx, gate, qvec,
    grid_ra, grid_dec, center, thresh, use_kernel=False, block_rows=None,
):
    return _scan_clip(
        pixels, wcs, ints, floats, psf_kernels, gate, qvec, grid_ra, grid_dec,
        center, thresh, use_kernel, block_rows, pack_idx=pack_idx,
    )


@partial(jax.jit, static_argnames=("use_kernel", "block_rows"))
def _moments_scan_batch_sparse(
    pixels, wcs, ints, floats, psf_kernels, pack_idx, gates, qvecs,
    grids_ra, grids_dec, use_kernel=False, block_rows=None,
):
    def one(gate, qvec, grid_ra, grid_dec):
        return _scan_moments(
            pixels, wcs, ints, floats, psf_kernels, gate, qvec, grid_ra,
            grid_dec, use_kernel, block_rows, pack_idx=pack_idx,
        )

    return jax.vmap(one)(gates, qvecs, grids_ra, grids_dec)


@partial(jax.jit, static_argnames=("use_kernel", "block_rows", "nbins"))
def _hist_scan_batch_sparse(
    pixels, wcs, ints, floats, psf_kernels, pack_idx, gates, qvecs,
    grids_ra, grids_dec, los, inv_ws, nbins=16, use_kernel=False,
    block_rows=None,
):
    def one(gate, qvec, grid_ra, grid_dec, lo, inv_w):
        return (_scan_hist(
            pixels, wcs, ints, floats, psf_kernels, gate, qvec, grid_ra,
            grid_dec, lo, inv_w, nbins, use_kernel, block_rows,
            pack_idx=pack_idx,
        ),)

    return jax.vmap(one)(gates, qvecs, grids_ra, grids_dec, los, inv_ws)


@partial(jax.jit, static_argnames=("use_kernel", "block_rows"))
def _clip_scan_batch_sparse(
    pixels, wcs, ints, floats, psf_kernels, pack_idx, gates, qvecs,
    grids_ra, grids_dec, centers, threshs, use_kernel=False, block_rows=None,
):
    def one(gate, qvec, grid_ra, grid_dec, center, thresh):
        return _scan_clip(
            pixels, wcs, ints, floats, psf_kernels, gate, qvec, grid_ra,
            grid_dec, center, thresh, use_kernel, block_rows,
            pack_idx=pack_idx,
        )

    return jax.vmap(one)(gates, qvecs, grids_ra, grids_dec, centers, threshs)


# Between-pass operand computation, jitted so the streaming passes share one
# compiled formula (the center/threshold math never runs on the host).
@jax.jit
def _clip_operands(s0, s1, s2, clip_k):
    mu, sigma = reducer.clip_stats(s0, s1, s2)
    return mu, reducer.clip_threshold(mu, sigma, clip_k)


@partial(jax.jit, static_argnames=("nbins",))
def _hist_operands(s0, s1, s2, nbins=16):
    return reducer.hist_bounds(s0, s1, s2, nbins)


@jax.jit
def _median_operands(hist, s0, s1, s2, lo, w, clip_k):
    _, sigma = reducer.clip_stats(s0, s1, s2)
    center = reducer.hist_median(hist, s0, lo, w)
    return center, reducer.clip_threshold(center, sigma, clip_k)


@jax.jit
def _match_packs(pixels, kernels):
    """Query-independent PSF matching of resident packs, on device.

    (P, cap, H, W) pixels x (P, cap, ...) kernel bank -> matched pixels of
    the same shape.  `lax.map` steps the pack axis so each step convolves
    one (cap, H, W) pack — the *same* inner program `mapper.map_batch` runs
    when the bank is threaded into a dispatch, which is what makes cached
    and uncached matched pixels bitwise-identical (parity-tested).  No host
    bytes move: both operands are already resident.
    """
    return jax.lax.map(
        lambda xs: psf.convolve_batch(xs[0], xs[1]), (pixels, kernels)
    )


@partial(jax.jit, static_argnames=("npix", "use_kernel"))
def _mosaic_bricks(tiles, covs, offsets, npix, use_kernel=False):
    """Merge cached brick tiles into one (npix, npix) mosaic (DESIGN.md §9).

    One jitted dispatch over (B, b, b) device-resident brick coadds +
    weight maps and their (B, 2) output offsets.  The XLA scan and the
    Pallas kernel accumulate into a zero canvas in the same brick order,
    so both match the fresh lattice-window scan bitwise.
    """
    if use_kernel:
        return warp_ops.mosaic_bricks(tiles, covs, offsets, npix)
    return reducer.mosaic_tiles(tiles, covs, offsets, npix)


def _sync(x):
    """The streaming executors' ONE host sync, at reduce time (DESIGN.md §6).

    Every window dispatch and every chunk upload before this point is
    asynchronous — the device scans window N while the host enqueues the
    N+1 upload — so a streaming query's wall clock is max(upload, compute)
    per window, not their sum.  Tests monkeypatch this to pin the
    block-only-at-reduce-time contract.
    """
    return jax.block_until_ready(x)


class CoaddEngine:
    """Plans queries on the host, executes them against resident layouts.

    Pixels cross host->device exactly once per layout (`device_dataset`) and
    host->mesh exactly once per (layout, mesh) (`mesh_dataset`); every query
    — single, batched, or distributed — is a single jitted dispatch.  Set
    ``use_kernel=True`` to fuse map+reduce through the Pallas ``coadd_fused``
    kernel (lowered through Mosaic on a TPU, interpreted on the CPU; see
    `repro.kernels.interpret_mode`), and ``match_psf_sigma`` to convolve
    every image to a common PSF width in the map stage before warping.
    """

    def __init__(
        self,
        survey: Survey,
        pack_capacity: int = 64,
        use_kernel: bool = False,
        block_rows: Optional[int] = None,
        match_psf_sigma: Optional[float] = None,
        measured_psf: Optional[bool] = None,
        matched_pixel_cache: bool = True,
        sparse: bool = True,
        device_budget_bytes: Optional[int] = None,
        stream_chunk_packs: Optional[int] = None,
        on_fault: str = "retry",
        fault_max_attempts: int = 3,
        fault_backoff_s: float = 0.05,
        straggler_factor: Optional[float] = None,
        verify_digests: bool = False,
        fault_injector: Optional[ChaosInjector] = None,
        brick_deg: float = 0.25,
        brick_npix: int = 64,
        journal_dir: Optional[str] = None,
        journal_max_age_s: float = 7 * 86400.0,
        clip_k: float = 3.0,
        median_bins: int = 16,
    ):
        self.survey = survey
        # Robust-reduction knobs (DESIGN.md §11): the sigma-clip radius and
        # the binapprox histogram resolution shared by every executor.  Part
        # of `result_key` for robust plans — two engines with different knobs
        # must never share cached bytes.
        self.clip_k = float(clip_k)
        self.median_bins = int(median_bins)
        self.use_kernel = use_kernel
        self.block_rows = block_rows  # None -> autotune per (npix, H, W)
        self.match_psf_sigma = match_psf_sigma
        # Measured-PSF homogenization (DESIGN.md §7): None = auto (use the
        # survey's empirical stamps when present, separable Gaussian bank
        # otherwise); True forces stamps (loud error if absent); False
        # forces the Gaussian fallback — the parity-test baseline.
        self.measured_psf = measured_psf
        # Matched-pixel residency cache (§7): on the XLA map path the
        # matching convolution is query-independent, so convolve ONCE per
        # (layout, target) at residency time and cache the matched pixels
        # under the device budget, instead of re-convolving inside every
        # dispatch.  The Pallas path keeps its in-kernel recompute (the
        # documented fusion tradeoff), so this flag is inert there.
        self.matched_pixel_cache = matched_pixel_cache
        # Sparse execution (DESIGN.md §5): gather only the packs a gate
        # opens before scanning, and reblock degenerate layouts at residency
        # time.  False reproduces the dense masked-discard scan over every
        # pack — kept as the parity/benchmark baseline.
        self.sparse = sparse
        # Streaming residency (DESIGN.md §6): with a device budget set,
        # layouts stop uploading eagerly; queries scan budget-sized chunk
        # windows with uploads double-buffered behind compute, and the
        # ResidencyManager LRU-evicts cold chunks — archives larger than
        # device memory run correctly, just with more windows.
        self.device_budget_bytes = device_budget_bytes
        self.stream_chunk_packs = stream_chunk_packs  # None -> budget/2 sizing
        # Fault policy (DESIGN.md §8): how the streaming executors respond
        # to upload failures, poisoned chunks, and stragglers.
        #   "retry"      — WindowTracker re-executes transient failures with
        #                  capped exponential backoff (the default);
        #   "quarantine" — like retry, but persistent poison gates the bad
        #                  packs out and the query completes partial=True;
        #   "raise"      — no tracker at all: any fault aborts the query
        #                  (the zero-overhead baseline BENCH compares against).
        if on_fault not in ("retry", "quarantine", "raise"):
            raise ValueError(
                f"on_fault must be 'retry', 'quarantine', or 'raise'; "
                f"got {on_fault!r}"
            )
        self.on_fault = on_fault
        self.fault_max_attempts = fault_max_attempts
        self.fault_backoff_s = fault_backoff_s
        # Speculative re-execution of straggler windows (off by default):
        # timing a window means blocking on it, so enabling this trades the
        # one-sync-at-reduce-time contract for straggler detection — the
        # documented speculation cost (§8).
        self.straggler_factor = straggler_factor
        # Chunk verification scope: the NaN/Inf scan always runs on tracked
        # builds; digest comparison against the host seqfile (catches finite
        # corruption) is opt-in because it costs a sha256 per pack per build.
        self.verify_digests = verify_digests
        self.fault_injector = fault_injector
        # Window-partial journals of killed queries, keyed by job key and
        # capped: a re-issued query replays only its missing windows.
        self._journals: "OrderedDict[str, Dict]" = OrderedDict()
        self._journal_cap = 16
        # Durable fault domain (DESIGN.md §8): with ``journal_dir`` set,
        # window journals write through to crash-safe on-disk segments
        # (`durable.JournalStore`) and the BrickStore host tier persists
        # (`durable.BrickSpill`) — a SIGKILLed query or materialization
        # resumes bitwise in a *fresh process*.  Journals of completed jobs
        # are removed atomically; orphans older than ``journal_max_age_s``
        # are swept here at init.
        self.journal_dir = journal_dir
        self.journal_store: Optional[JournalStore] = None
        brick_spill = None
        if journal_dir is not None:
            self.journal_store = JournalStore(
                os.path.join(journal_dir, "windows"),
                max_age_s=journal_max_age_s,
            )
            brick_spill = BrickSpill(os.path.join(journal_dir, "bricks"))
        # Quarantine releases since the last streaming result, reported as
        # JobStats.requarantine_released by the next query (additive).
        self._requarantine_pending = 0
        self.residency = ResidencyManager(device_budget_bytes)
        if fault_injector is not None:
            self.residency.fault_hook = fault_injector.on_upload
        self.camcol_dec = camcol_dec_table(survey)
        self.sql = SpatialIndex.build(survey)
        self._datasets: Dict[str, PackedDataset] = {}
        self._exec_cache: Dict[str, Tuple[PackedDataset, Optional[SlotRemap]]] = {}
        self._device_cache: Dict[str, DevicePackedDataset] = {}
        self._mesh_cache: Dict[Tuple, MeshResidentDataset] = {}
        self._psf_banks: Dict[Tuple, np.ndarray] = {}
        self._psf_device: Dict[Tuple, "jax.Array"] = {}
        self._pack_capacity = pack_capacity
        self.pack_upload_count = 0   # host->device uploads of pack pixels
        self.mesh_upload_count = 0   # host->mesh uploads of whole layouts
        self.dispatch_count = 0      # jitted device dispatches issued
        self.matched_builds = 0      # device-side matched-pixel constructions
        # Brick tessellation (DESIGN.md §9): the materialized-coadd tier.
        # The grid is built lazily from the survey footprint; the store
        # shares the engine's ResidencyManager so brick tiles compete with
        # streaming chunks under one device budget (at COST_BRICK priority).
        self.brick_deg = brick_deg
        self.brick_npix = brick_npix
        self._brick_grid: Optional[BrickGrid] = None
        self.brick_store = BrickStore(self.residency, spill=brick_spill)

    # ----- dataset layouts (built lazily, cached) -----
    def dataset(self, layout: str) -> PackedDataset:
        if layout not in self._datasets:
            if layout == "per_file":
                self._datasets[layout] = pack_per_file(self.survey)
            elif layout == "unstructured":
                self._datasets[layout] = pack_unstructured(
                    self.survey, self._pack_capacity
                )
            elif layout == "structured":
                self._datasets[layout] = pack_structured(
                    self.survey, self._pack_capacity
                )
            else:
                raise ValueError(layout)
        return self._datasets[layout]

    def exec_dataset(self, layout: str) -> Tuple[PackedDataset, Optional[SlotRemap]]:
        """Execution-side form of a layout + the gate remap onto it.

        Planning always sees the layout as the method defines it (per-file
        gating stays per-file); execution may re-pack it for scan efficiency.
        The per-file layout's (P=N, cap=1) geometry makes every scan step a
        one-image pack — pure scan overhead — so under sparse execution it is
        reblocked into dense ``pack_capacity``-slot super-packs at residency
        time, and plan gates are rewritten through the returned `SlotRemap`.
        """
        if layout not in self._exec_cache:
            ds = self.dataset(layout)
            if self.sparse and layout == "per_file" and ds.capacity < self._pack_capacity:
                self._exec_cache[layout] = ds.reblock(self._pack_capacity)
            else:
                self._exec_cache[layout] = (ds, None)
        return self._exec_cache[layout]

    def device_dataset(self, layout: str) -> DevicePackedDataset:
        """Device-resident form of a layout; uploaded once, then cached."""
        if layout not in self._device_cache:
            exec_ds, _ = self.exec_dataset(layout)
            self._device_cache[layout] = exec_ds.to_device()
            self.pack_upload_count += 1
        return self._device_cache[layout]

    def mesh_dataset(
        self, layout: str, mesh: Mesh, shard_axes: Tuple[str, ...]
    ) -> MeshResidentDataset:
        """Mesh-resident form of a layout; sharded once per (layout, mesh).

        A cache hit means a distributed job moves zero pixel bytes: its only
        host->mesh traffic is slot gates + query vectors + output grids.
        The key carries the PSF state because the sharded dataset bakes in
        its kernel bank — a retuned engine must re-shard, not silently
        serve the old configuration's kernels.
        """
        key = (layout, mesh, tuple(shard_axes), self._psf_state())
        if key not in self._mesh_cache:
            # Retune hygiene: one sharded copy per (layout, mesh, axes) —
            # drop the old target's rather than pinning every historical one.
            for k in [k for k in self._mesh_cache if k[:3] == key[:3]]:
                del self._mesh_cache[k]
            exec_ds, _ = self.exec_dataset(layout)
            self._mesh_cache[key] = exec_ds.to_mesh(
                mesh, tuple(shard_axes), psf_kernels=self.psf_kernel_bank(layout)
            )
            self.mesh_upload_count += 1
        return self._mesh_cache[key]

    # ----- PSF matching (kernel banks precomputed on host, cached) -----
    def _psf_state(self) -> Optional[Tuple]:
        """Hashable id of the PSF configuration every kernel bank, matched-
        pixel entry, chunk and mesh dataset derives from — (target,
        measured-mode), or None when matching is off.  Every such cache
        keys on this, so retuning either knob (the supported live-mutation
        flow) misses instead of silently serving stale kernels."""
        if self.match_psf_sigma is None:
            return None
        return (float(self.match_psf_sigma), self.measured_psf)

    def psf_kernel_bank(self, layout: str) -> Optional[np.ndarray]:
        """Per-slot matching kernels, or None when matching is disabled.

        (P, cap, K, K) measured-PSF homogenization kernels when the layout
        carries empirical stamps (`psf.homogenization_bank` — Fourier least
        squares to the Gaussian target), the separable (P, cap, K) Gaussian
        bank otherwise; ``measured_psf`` forces either side.  Built against
        the *execution* form so the bank lines up slot-for-slot with the
        resident (possibly reblocked) arrays.
        """
        if self.match_psf_sigma is None:
            return None
        # Keyed per (layout, psf-state), like the matched-pixel entries: an
        # engine retuned to a new target or measured-mode must never reuse
        # stale kernels.
        key = (layout, self._psf_state())
        if key not in self._psf_banks:
            # Retune hygiene: keep one host bank per layout.
            for k in [k for k in self._psf_banks if k[0] == layout]:
                del self._psf_banks[k]
            exec_ds, _ = self.exec_dataset(layout)
            measured = (
                self.measured_psf if self.measured_psf is not None
                else exec_ds.psf_stamps is not None
            )
            if measured:
                if exec_ds.psf_stamps is None:
                    raise ValueError(
                        "measured_psf=True but the survey carries no PSF "
                        "stamps (SurveyConfig.psf_stamps)"
                    )
                self._psf_banks[key] = psf.homogenization_bank(
                    exec_ds.psf_stamps,
                    exec_ds.floats["psf_sigma"],
                    self.match_psf_sigma,
                )
            else:
                self._psf_banks[key] = psf.matching_kernel_bank(
                    exec_ds.floats["psf_sigma"], self.match_psf_sigma
                )
        return self._psf_banks[key]

    def _device_psf_kernels(self, layout: str):
        bank = self.psf_kernel_bank(layout)
        if bank is None:
            return None
        key = (layout, self._psf_state())
        if key not in self._psf_device:
            # Retune hygiene: one device bank per layout — drop the old
            # target's copy rather than pinning every historical one.
            for k in [k for k in self._psf_device if k[0] == layout]:
                del self._psf_device[k]
            self._psf_device[key] = jnp.asarray(bank)
        return self._psf_device[key]

    # ----- matched-pixel residency cache (DESIGN.md §7) -----
    def _matched_mode(self) -> bool:
        """Whether dispatches read cached matched pixels instead of a bank.

        Only the XLA map path qualifies: the Pallas kernel fuses the
        convolution into the warp on purpose (recompute-for-fusion), so
        caching would buy it nothing but HBM.
        """
        return (
            self.match_psf_sigma is not None
            and not self.use_kernel
            and self.matched_pixel_cache
        )

    def _matched_device_dataset(
        self, layout: str, dev: DevicePackedDataset
    ) -> Tuple[DevicePackedDataset, int]:
        """The eager layout with pixels replaced by PSF-matched pixels.

        A *derived* residency entry keyed (layout, target): built once per
        engine by convolving the resident pixels with the device bank —
        on-device compute, zero H2D — and served from the LRU afterwards.
        Metadata/wcs alias the raw resident arrays, so the cache charges
        only the matched pixel bytes.  Returns (dataset, hits) where hits
        is 1 when the entry was already resident.
        """
        key = ("matched", layout, self._psf_state())
        # Retune hygiene: the eager manager never evicts (budget None), so
        # shed the previous target's whole-layout matched copy explicitly —
        # retunes must not pin one full pixel array per historical target.
        self.residency.drop_matching(
            lambda k: k[:2] == ("matched", layout) and k != key
        )
        hits0 = self.residency.hits

        def build():
            kern = self._device_psf_kernels(layout)
            self.matched_builds += 1
            return DevicePackedDataset(
                pixels=_match_packs(dev.pixels, kern),
                wcs=dev.wcs,
                ints=dev.ints,
                floats=dev.floats,
            )

        payload = self.residency.acquire(
            key, int(dev.pixels.nbytes), build, h2d=False,
            cost=COST_MATCHED_CHUNK,
        )
        return payload, self.residency.hits - hits0

    # ----- streaming residency (DESIGN.md §6) -----
    def _bank_pack_nbytes(self, layout: str) -> int:
        """Resident bytes ONE pack's PSF matching-kernel bank adds (0 when
        matching is off) — charged alongside pixel bytes so the budget
        bounds everything a chunk keeps on device."""
        bank = self.psf_kernel_bank(layout)
        return 0 if bank is None else bank[0].nbytes

    def _chunk_packs(self, exec_ds: PackedDataset) -> int:
        """Packs per residency chunk: half the budget, so two chunks —
        the one being scanned and the one uploading behind it — fit
        resident simultaneously (double buffering)."""
        if self.stream_chunk_packs is not None:
            return max(1, min(self.stream_chunk_packs, exec_ds.n_packs))
        pack_bytes = max(
            exec_ds.pack_nbytes() + self._bank_pack_nbytes(exec_ds.layout), 1
        )
        fit = int(self.device_budget_bytes // (2 * pack_bytes))
        return max(1, min(fit, exec_ds.n_packs))

    @property
    def _fault_tolerant(self) -> bool:
        """Whether streaming queries run through the WindowTracker (§8)."""
        return self.on_fault != "raise"

    @property
    def _verify_chunks(self) -> bool:
        """Whether chunk builds stage-and-verify host pixels before upload.

        On whenever faults are handled *or* injected: with ``on_fault=
        "raise"`` plus an injector, poison is still detected — it just
        aborts the query (the loud baseline) instead of healing.
        """
        return self._fault_tolerant or self.fault_injector is not None

    def _staged_chunk_pixels(
        self, exec_ds: PackedDataset, start: int, stop: int,
        drop: FrozenSet[int],
    ) -> Optional[np.ndarray]:
        """Stage, verify, and sanitize a chunk's host pixels (DESIGN.md §8).

        Returns the pixel array `to_device_chunk` should upload, or None to
        upload the seqfile slice directly (verification off).  Injection
        corrupts a *copy*; detection (NaN/Inf scan, plus digest comparison
        against the host seqfile under ``verify_digests``) raises
        `PoisonedChunkError` with the offending global pack indices; packs
        in ``drop`` (already quarantined) are zeroed instead — pixel zeros,
        not just gate falses, because a NaN surviving into the masked scan
        would still poison the accumulator (NaN * 0 == NaN).
        """
        if not self._verify_chunks:
            return None
        px = exec_ds.pixels[start:stop]
        if self.fault_injector is not None:
            px = self.fault_injector.corrupt_chunk(start, stop, px)
        drop_local = sorted(p - start for p in drop if start <= p < stop)
        bad = exec_ds.verify_chunk(
            start, stop, px,
            skip=frozenset(p + start for p in drop_local),
            check_digests=self.verify_digests,
        )
        if bad:
            raise PoisonedChunkError(bad)
        if drop_local:
            if not px.flags.owndata:  # still a seqfile view: copy before zeroing
                px = np.array(px, copy=True)
            px[drop_local] = 0.0
        return px

    def _resident_chunk(self, layout: str, exec_ds: PackedDataset,
                        start: int, stop: int,
                        drop: FrozenSet[int] = frozenset()):
        """(DevicePackedDataset, psf chunk) for packs [start, stop), via LRU.

        In matched mode (§7) the chunk *is* the matched-pixel cache: the
        raw pixels upload once, the query-independent matching convolution
        runs on device right behind the transfer, and only the matched
        chunk stays resident — repeat queries hit the LRU and pay neither
        the upload nor the convolution.  The key carries the PSF target so
        engines retuned to a different target never alias.

        ``drop`` lists quarantined global packs (§8): their rows upload as
        zeros and the key carries them, so a sanitized chunk never aliases
        the clean one.
        """
        matched = self._matched_mode()
        # The payload embeds PSF state either way (matched pixels, or the
        # bank slice riding alongside), so the key always carries the
        # psf-state: a retuned engine must miss, not reuse stale kernels.
        state = self._psf_state()
        key = (
            (layout, start, stop, "matched", state)
            if matched else (layout, start, stop, state)
        )
        drop_here = tuple(sorted(p for p in drop if start <= p < stop))
        if drop_here:
            key = key + ("quarantine", drop_here)

        def build():
            staged = self._staged_chunk_pixels(exec_ds, start, stop, drop)
            dev = exec_ds.to_device_chunk(start, stop, pixels=staged)
            bank = self.psf_kernel_bank(layout)
            self.pack_upload_count += 1
            if matched:
                self.matched_builds += 1
                dev = DevicePackedDataset(
                    pixels=_match_packs(
                        dev.pixels, jnp.asarray(bank[start:stop])
                    ),
                    wcs=dev.wcs,
                    ints=dev.ints,
                    floats=dev.floats,
                )
                return (dev, None)
            kern = None if bank is None else jax.device_put(bank[start:stop])
            return (dev, kern)

        nbytes = exec_ds.chunk_nbytes(start, stop) + (
            0 if matched
            else (stop - start) * self._bank_pack_nbytes(layout)
        )
        # A matched build transiently holds the raw pixel chunk AND the
        # bank slice alive next to its matched copy until the convolution
        # retires — declare both so peak_bytes reports the true build-time
        # footprint.  (The unmatched branch's bank slice stays resident and
        # is already counted inside ``nbytes``.)
        transient = (
            (int(exec_ds.pixels[0].nbytes) + self._bank_pack_nbytes(layout))
            * (stop - start)
            if matched else 0
        )
        return self.residency.acquire(
            key, nbytes, build, transient_bytes=transient,
            cost=COST_MATCHED_CHUNK if matched else COST_RAW_CHUNK,
        )

    # ----- shared helpers -----
    def _plan_grids(self, plan: CoaddPlan):
        """The plan's output grid on the device and the bytes its build
        sent up: ``(grid_ra, grid_dec, h2d_bytes)``.

        The query's own TAN grid is built on the device from its WCS
        vector (`mapper.query_grid`), so 48 bytes go up; a `grid_sky`
        override (brick-lattice plans, §9) is the float64 host lattice
        cast to float32, and is uploaded whole."""
        if plan.grid_sky is not None:
            gr, gd = (jnp.asarray(a) for a in plan.grid_sky)
            return gr, gd, _nbytes(gr, gd)
        gvec = mapper.grid_vector(plan.query.grid_wcs_vector())
        return (*mapper.query_grid(gvec, plan.query.npix), gvec.nbytes)

    @staticmethod
    def _grid_tag(plan: CoaddPlan) -> str:
        """Journal-identity tag of a plan's grid override (empty = default).

        `_job_key` must distinguish a lattice-window scan from the plain
        query-grid scan of the same bounds: their window partials differ
        bitwise, so replaying one journal into the other would be wrong.
        """
        return grid_digest(plan.grid_sky)

    def _block_rows(self, query: CoaddQuery, ds: PackedDataset) -> Optional[int]:
        """The Pallas lane's output block (None on the XLA lane).

        Raises ``ValueError`` before any dispatch when the kernel lane is
        asked to warp frames too large for one VMEM grid step: the engine
        refuses such a query rather than falling back to the XLA lane.
        """
        if not self.use_kernel or self.block_rows is not None:
            return self.block_rows
        h, w = ds.image_hw()
        bank = self.psf_kernel_bank(ds.layout)
        return warp_ops.autotune_block_rows(
            query.npix, h, w,
            psf_kernel_width=0 if bank is None else bank.shape[-1],
            psf_kernel_2d=bank is not None and bank.ndim == 4,
        )

    def _batch_window_fit(self, plans, exec_ds: PackedDataset, gates,
                          psf_in_dispatch: bool,
                          pixels) -> Optional[windowed.WindowFit]:
        """One windowed shape for a batch (`windowed.common_fit`): each
        query's scan is then the program its solo `execute` runs.  A batch
        whose queries do not all fit alike keeps the gather for all."""
        return windowed.common_fit([
            self._window_fit(p, exec_ds, g, psf_in_dispatch, pixels)
            for p, g in zip(plans, gates)])

    def _window_fit(self, plan: CoaddPlan, exec_ds: PackedDataset,
                    gate: np.ndarray, psf_in_dispatch: bool,
                    pixels) -> Optional[windowed.WindowFit]:
        """The windowed warp's static shape for a mean scan, or None.

        Chosen by what the plan and the data show, not by a knob: resident
        ``pixels`` in a layout the kernel reads (`windowed.lane_axis`: on a
        TPU), the XLA lane of a mean stack with no in-dispatch PSF
        convolution, the query's own TAN grid, and every gated frame's
        footprint over an output tile fitting a source window
        (`windowed.window_fit`: at native scale and a small rotation it
        does).  Anything else keeps the XLA gather.
        """
        lanes = windowed.lane_axis(pixels)
        if (lanes is None or self.use_kernel or psf_in_dispatch
                or plan.reduce != "mean" or plan.grid_sky is not None):
            return None
        h, w = exec_ds.image_hw()
        return windowed.window_fit(
            plan.query.grid_wcs_vector(), exec_ds.wcs[gate],
            plan.query.npix, h, w, exec_ds.capacity, lanes,
        )

    # ----- planning: the six methods differ ONLY in gate construction -----
    def plan(self, query: CoaddQuery, method: str,
             reduce: str = "mean") -> CoaddPlan:
        with span("plan"):
            if method not in METHODS:
                raise ValueError(
                    f"unknown method {method}; expected one of {METHODS}")
            if reduce not in reducer.REDUCERS:
                raise ValueError(
                    f"unknown reduce {reduce!r}; expected one of {reducer.REDUCERS}"
                )
            plan = getattr(self, f"plan_{method}")(query)
            # The reduction variant is plan state (it changes the result
            # bytes): set after the method planner so all six stay
            # reduce-agnostic.
            plan.reduce = reduce
            return plan

    def plan_raw_fits(self, query: CoaddQuery) -> CoaddPlan:
        ds = self.dataset("per_file")
        with span("plan.locate") as locate:
            # No prefilter: every file is "located" and becomes a mapper input.
            gate = ds.valid.copy()
        return CoaddPlan("raw_fits", "per_file", gate, _query_vec(query),
                         query, locate.seconds, psf_target=self.match_psf_sigma)

    def plan_raw_fits_prefiltered(self, query: CoaddQuery) -> CoaddPlan:
        ds = self.dataset("per_file")
        with span("plan.locate") as locate:
            mask = glob_file_mask(self.survey.meta_table(), query, self.camcol_dec)
            gate = ds.valid & mask[:, None]  # per-file layout: pack == file
        return CoaddPlan("raw_fits_prefiltered", "per_file", gate,
                         _query_vec(query), query, locate.seconds,
                         psf_target=self.match_psf_sigma)

    def plan_unstructured_seq(self, query: CoaddQuery) -> CoaddPlan:
        ds = self.dataset("unstructured")
        with span("plan.locate") as locate:
            gate = ds.valid.copy()  # unprunable by construction: read every pack
        return CoaddPlan("unstructured_seq", "unstructured", gate,
                         _query_vec(query), query, locate.seconds,
                         psf_target=self.match_psf_sigma)

    def plan_structured_seq_prefiltered(self, query: CoaddQuery) -> CoaddPlan:
        ds = self.dataset("structured")
        with span("plan.locate") as locate:
            mask = glob_pack_mask(ds, query, self.camcol_dec)
            gate = ds.valid & mask[:, None]
        return CoaddPlan("structured_seq_prefiltered", "structured", gate,
                         _query_vec(query), query, locate.seconds,
                         psf_target=self.match_psf_sigma)

    def _plan_sql(self, layout: str, query: CoaddQuery, method: str) -> CoaddPlan:
        ds = self.dataset(layout)
        with span("plan.locate") as locate:
            ids = self.sql.select(query)
            # The index maps ids -> (pack, slot); the "gather" is a
            # metadata-only slot gate over the resident containers, so exact
            # selection costs no pixel movement at all.
            gate = ds.slot_mask(ids)
        return CoaddPlan(method, layout, gate, _query_vec(query), query,
                         locate.seconds, psf_target=self.match_psf_sigma)

    def plan_sql_unstructured(self, query: CoaddQuery) -> CoaddPlan:
        return self._plan_sql("unstructured", query, "sql_unstructured")

    def plan_sql_structured(self, query: CoaddQuery) -> CoaddPlan:
        return self._plan_sql("structured", query, "sql_structured")

    def _exec_gate(self, plan: CoaddPlan) -> np.ndarray:
        """A plan's gate in execution-layout coordinates (remapped if reblocked)."""
        _, remap = self.exec_dataset(plan.layout)
        return remap.apply(plan.gate) if remap is not None else plan.gate

    def _sparse_index(self, gate_or_gates: np.ndarray) -> Optional[SparseScanIndex]:
        """The gather plan for a gate (or gate stack), or None for dense.

        Sparse execution only pays when the budget bucket is smaller than
        the layout — a full-archive gate (raw_fits, unstructured_seq)
        degrades gracefully to the dense scan of the same program shape.
        """
        if not self.sparse:
            return None
        sp = (
            union_sparse_index(gate_or_gates)
            if gate_or_gates.ndim == 3
            else sparse_pack_index(gate_or_gates)
        )
        return sp if sp.worthwhile else None

    def _stream_windows(self, exec_ds: PackedDataset,
                        gate_any: np.ndarray) -> List[ScanWindow]:
        """Chunk-aligned window schedule for a (P,)-any gate (or all packs
        when sparse execution is off — dense semantics scan everything)."""
        if self.sparse:
            gated = np.nonzero(gate_any)[0]
        else:
            gated = np.arange(exec_ds.n_packs)
        return window_schedule(gated, exec_ds.n_packs,
                               self._chunk_packs(exec_ds))

    def _job_key(self, method: str, layout: str, gates: np.ndarray,
                 qvecs: np.ndarray, npix: int,
                 windows: List[ScanWindow], grid_tag: str = "") -> str:
        """Cross-query identity of a streaming job's window journal (§8).

        A digest over everything that determines a window partial's value —
        method/layout/PSF state, the gate and query-vector bytes, the output
        grid size, the window partition itself, and the persistent
        quarantine set (a pack released between kill and resume changes the
        partials bitwise, so the resumed job must miss, not replay) — so a
        resumed query replays journaled partials only when they are
        bitwise-valid for it.
        """
        quar = tuple(sorted(self.residency.quarantined_packs(layout)))
        h = hashlib.sha256()
        h.update(
            f"{method}|{layout}|{npix}|{self._psf_state()}|{grid_tag}"
            f"|q{quar}".encode()
        )
        h.update(np.ascontiguousarray(gates).tobytes())
        h.update(np.ascontiguousarray(qvecs, np.float32).tobytes())
        for w in windows:
            h.update(
                np.array([w.start, w.stop, w.n_gated, w.budget], np.int64)
                .tobytes()
            )
        return h.hexdigest()

    def _journal_for(self, job_key: str) -> Dict:
        """The (possibly resumed) window journal for a job, LRU-capped.

        In-memory dict by default; with ``journal_dir`` a `DiskJournal`
        that replays any valid on-disk prefix at open — the resume path for
        a *fresh process* (the cap then only bounds open handles; disk
        state is untouched until completion removes it).
        """
        journal = self._journals.get(job_key)
        if journal is None:
            if self.journal_store is not None:
                journal = self.journal_store.open(job_key)
            else:
                journal = {}
            self._journals[job_key] = journal
            while len(self._journals) > self._journal_cap:
                _, old = self._journals.popitem(last=False)
                if hasattr(old, "close"):
                    old.close()
        else:
            self._journals.move_to_end(job_key)
        return journal

    def reverify_quarantined(self, layout: Optional[str] = None) -> List[int]:
        """Re-verify quarantined packs against the host seqfile (§8).

        Quarantine auto-release: for every registered layout (or just
        ``layout``), re-hash the quarantined packs' *current* host pixels;
        packs that verify — repaired in place, or never host-corrupt at all
        — leave the registry and regain gate coverage on the next query.
        Returns the released global pack indices; the count also surfaces as
        ``JobStats.requarantine_released`` on the next streaming result.
        """
        layouts = (
            [layout] if layout is not None
            else list(self.residency.quarantined)
        )
        released: List[int] = []
        for lay in layouts:
            exec_ds, _ = self.exec_dataset(lay)
            released.extend(self.residency.reverify_quarantined(lay, exec_ds))
        self._requarantine_pending += len(released)
        return released

    def _take_requarantine_released(self) -> int:
        n, self._requarantine_pending = self._requarantine_pending, 0
        return n

    def _empty_streaming_result(self, plan: CoaddPlan) -> CoaddResult:
        """The empty-selection answer under a device budget: exact zeros,
        zero windows, zero uploads.  Streaming's analogue of the §5
        empty-gate contract — and the guard that keeps the window-stat
        reductions (`max` over budgets) off an empty schedule entirely."""
        npix = plan.query.npix
        stats = JobStats(
            method=plan.method,
            files_considered=0,
            files_contributing=0,
            packs_touched=0,
            t_locate_s=plan.t_locate_s,
            t_map_reduce_s=0.0,
            t_total_s=plan.t_locate_s,
            dispatches=0,
            peak_resident_bytes=self._peak_resident_bytes(),
        )
        return CoaddResult(
            np.zeros((npix, npix), np.float32),
            np.zeros((npix, npix), np.float32),
            stats,
        )

    def _retire_journal(self, job_key: str) -> None:
        """Drop a completed job's window journal (memory + disk)."""
        old = self._journals.pop(job_key, None)
        if hasattr(old, "close"):
            old.close()
        if self.journal_store is not None:
            self.journal_store.remove(job_key)

    def _run_stream_windows(self, layout: str, exec_ds: PackedDataset,
                            windows: List[ScanWindow], dispatch,
                            job_key: str, keep_journal: bool = False):
        """Walk a window schedule: dispatch each window against its
        resident chunk, prefetch the next chunk (its async `device_put`
        rides behind the in-flight scan — the double buffer), accumulate
        the additive window partials on device, and host-sync ONCE at
        reduce time.  ``dispatch(dev, kern, win, dropped)`` returns the
        partial tuple.

        With ``on_fault="raise"`` this is the bare PR 4 loop (any failure
        aborts the query — the zero-overhead baseline).  Otherwise every
        window runs through a `WindowTracker` (§8): journaled under
        ``job_key`` (a killed query resumes replaying only missing
        windows), retried on transient faults, optionally speculated, and
        quarantine-completed on persistent poison.

        Returns (partials, (uploads, hits, evictions), elapsed_s,
        FaultCounters, quarantined-pack tuple).
        """
        up0, hit0, ev0 = (self.residency.uploads, self.residency.hits,
                          self.residency.evictions)
        t1 = time.perf_counter()
        if not self._fault_tolerant:
            cur = self._resident_chunk(layout, exec_ds,
                                       windows[0].start, windows[0].stop)
            acc = None
            for i, win in enumerate(windows):
                dev, kern = cur
                out = dispatch(dev, kern, win, frozenset())
                acc = out if acc is None else tuple(
                    a + b for a, b in zip(acc, out)
                )
                if i + 1 < len(windows):
                    nxt = windows[i + 1]
                    cur = self._resident_chunk(layout, exec_ds,
                                               nxt.start, nxt.stop)
            fc, quarantined = FaultCounters(), ()
        else:
            pre_quar = self.residency.quarantined_packs(layout)
            tracker = WindowTracker(
                policy=self.on_fault,
                max_attempts=self.fault_max_attempts,
                backoff_s=self.fault_backoff_s,
                straggler_factor=self.straggler_factor,
                injector=self.fault_injector,
                quarantined=pre_quar,
            )
            acquire = lambda win, drop: self._resident_chunk(  # noqa: E731
                layout, exec_ds, win.start, win.stop, drop=drop
            )
            disp = lambda ops, win, drop: dispatch(  # noqa: E731
                ops[0], ops[1], win, drop
            )
            journal = self._journal_for(job_key)
            try:
                acc, quarantined = tracker.run(
                    windows, acquire, disp, journal
                )
            except BaseException:
                # Durability point: fsync the disk journal so a fatal (an
                # injected kill, an OOM about to follow) leaves every
                # finished window committed for the resume.  Clean
                # completion skips the barrier — the journal is removed
                # two lines below, so syncing it first buys nothing.
                if hasattr(journal, "drain"):
                    journal.drain()
                raise
            finally:
                # Fresh quarantines persist even when the query dies: the
                # registry (released only by `reverify_quarantined`) is
                # what lets later queries skip the poison without re-paying
                # the retry storm.
                fresh = tracker.quarantined - set(pre_quar)
                if fresh:
                    self.residency.quarantine_packs(
                        layout, fresh,
                        getattr(exec_ds, "_pack_digest_cache", None),
                    )
            # Completed: the journal has served its purpose.  (A kill or a
            # fatal error raises out above this line, *keeping* the journal
            # — that asymmetry is the resume contract, in-memory and on
            # disk alike; only clean completion garbage-collects.)  Robust
            # multi-pass jobs (§11) pass ``keep_journal=True``: a pass's
            # journal must outlive its own completion so a kill *between*
            # passes still replays it — the orchestrator retires every pass
            # journal together once the final pass completes.
            if not keep_journal:
                self._retire_journal(job_key)
            fc, quarantined = tracker.counters, tuple(quarantined)
        _sync(acc[0])
        elapsed = time.perf_counter() - t1
        counters = (self.residency.uploads - up0,
                    self.residency.hits - hit0,
                    self.residency.evictions - ev0)
        return acc, counters, elapsed, fc, quarantined

    def _execute_streaming(self, plan: CoaddPlan,
                           gather_only: bool = False) -> CoaddResult:
        """Windowed query under a device budget (DESIGN.md §6).

        The gated pack set is partitioned into residency-chunk windows;
        each window runs the §5 sparse program against its chunk while the
        next chunk's upload rides behind it (async `device_put`), and the
        window partials — the reduce monoid — accumulate on device.  The
        one host sync is `_sync` at the end: time-to-first-coadd no longer
        waits for the whole archive to land.
        """
        ds = self.dataset(plan.layout)
        exec_ds, _ = self.exec_dataset(plan.layout)
        gate = self._exec_gate(plan)
        if not gate.any():
            # Empty selection: answer zeros without building a window
            # schedule at all — no upload, no dispatch, and no window-stat
            # reduction over an empty list.
            return self._empty_streaming_result(plan)
        grid_ra, grid_dec, _ = self._plan_grids(plan)
        block_rows = self._block_rows(plan.query, ds)
        windows = self._stream_windows(exec_ds, gate.any(axis=1))
        qvec = jnp.asarray(plan.qvec)
        m_builds0, d0 = self.matched_builds, self.dispatch_count
        psf_in_dispatch = (self.psf_kernel_bank(plan.layout) is not None
                           and not self._matched_mode())

        def dispatch(dev, kern, win, dropped):
            g = gate
            if dropped:
                # Quarantined packs (§8): their pixels upload as zeros and
                # their slots gate False, so depth/files accounting excludes
                # them — the partial=True report is the honest answer.
                g = gate.copy()
                g[sorted(dropped)] = False
            self.dispatch_count += 1
            return _coadd_scan_sparse(
                dev.pixels,
                dev.wcs,
                dev.ints,
                dev.floats,
                kern,
                jnp.asarray(win.pack_idx),
                jnp.asarray(compact_window_gate(g, win)),
                qvec,
                grid_ra,
                grid_dec,
                use_kernel=self.use_kernel,
                block_rows=block_rows,
                window=None if gather_only else self._window_fit(
                    plan, exec_ds, gate, psf_in_dispatch, dev.pixels),
            )

        job_key = self._job_key(plan.method, plan.layout, gate, plan.qvec,
                                plan.query.npix, windows,
                                grid_tag=self._grid_tag(plan)
                                + ("|gather" if gather_only else ""))
        (coadd, depth, contrib, considered, steps), counters, elapsed, fc, \
            quar = self._run_stream_windows(plan.layout, exec_ds, windows,
                                            dispatch, job_key)
        steps = np.asarray(steps)
        if not _covered(steps):
            return self._execute_streaming(plan, gather_only=True)
        uploads, hits, evictions = counters
        # Coverage honesty: only quarantined packs this query's gate actually
        # opens are *uncovered* for it — persistent quarantine on packs the
        # query never wanted is not a partial answer.
        quar = tuple(p for p in quar if gate[p].any())
        stats = JobStats(
            method=plan.method,
            files_considered=int(considered),
            files_contributing=int(contrib),
            packs_touched=plan.packs_touched,
            t_locate_s=plan.t_locate_s,
            t_map_reduce_s=elapsed,
            t_total_s=plan.t_locate_s + elapsed,
            dispatches=self.dispatch_count - d0,
            packs_gated=int(gate.any(axis=1).sum()),
            packs_scanned=sum(w.budget for w in windows),
            scan_budget=max(w.budget for w in windows),
            windowed_packs=int(steps[0]),
            windows=len(windows),
            chunk_uploads=uploads,
            residency_hits=hits,
            residency_evictions=evictions,
            # In matched mode the chunk cache IS the matched-pixel cache:
            # a resident chunk hit reuses the convolution with the upload.
            matched_cache_builds=self.matched_builds - m_builds0,
            matched_cache_hits=hits if self._matched_mode() else 0,
            peak_resident_bytes=self._peak_resident_bytes(),
            retries=fc.retries,
            speculative_windows=fc.speculative_windows,
            quarantined_packs=fc.quarantined_packs,
            resumed_windows=fc.resumed_windows,
            partial=bool(quar),
            uncovered_packs=quar,
            requarantine_released=self._take_requarantine_released(),
        )
        return CoaddResult(np.asarray(coadd), np.asarray(depth), stats)

    def _reduce_tag(self, method: str, reduce: str, pass_tag: str) -> str:
        """Journal-identity tag of one robust pass: the method plus every
        engine knob that changes the pass's partial bytes, plus which pass
        this is — pass-1 moments and final clip partials of one query must
        never share a journal."""
        return (
            f"{method}|reduce={reduce}|k={self.clip_k}"
            f"|b={self.median_bins}|pass={pass_tag}"
        )

    def _execute_streaming_robust(self, plan: CoaddPlan) -> CoaddResult:
        """Robust reduce under a device budget: the multi-pass contract (§11).

        Each pass is an ordinary monoidal window stream: pass 1 accumulates
        the moments partials; ``median`` adds a binapprox-histogram pass;
        the final pass re-scans with the clip center/radius as fixed device
        operands.  Every pass journals under its own pass-tagged job key
        with ``keep_journal=True``, so a kill at ANY point — mid-pass or on
        the seam between passes — resumes by replaying the journaled
        windows bitwise; only when the final pass completes cleanly are all
        pass journals retired together.  Operands are recomputed from the
        replayed pass-1 partials on resume, so the recovered stack is
        bitwise-identical to the uninterrupted one.
        """
        ds = self.dataset(plan.layout)
        exec_ds, _ = self.exec_dataset(plan.layout)
        gate = self._exec_gate(plan)
        if not gate.any():
            res = self._empty_streaming_result(plan)
            res.stats.reduce = plan.reduce
            return res
        grid_ra, grid_dec, _ = self._plan_grids(plan)
        block_rows = self._block_rows(plan.query, ds)
        windows = self._stream_windows(exec_ds, gate.any(axis=1))
        qvec = jnp.asarray(plan.qvec)
        m_builds0, d0 = self.matched_builds, self.dispatch_count
        up = hi = ev = 0
        elapsed = 0.0
        fc = FaultCounters()
        pass_keys: List[str] = []
        quar: Tuple[int, ...] = ()

        def run_pass(tag: str, pass_fn, *extra):
            nonlocal up, hi, ev, elapsed, quar

            def dispatch(dev, kern, win, dropped):
                g = gate
                if dropped:
                    g = gate.copy()
                    g[sorted(dropped)] = False
                self.dispatch_count += 1
                return pass_fn(
                    dev.pixels, dev.wcs, dev.ints, dev.floats, kern,
                    jnp.asarray(win.pack_idx),
                    jnp.asarray(compact_window_gate(g, win)),
                    qvec, grid_ra, grid_dec, *extra,
                    use_kernel=self.use_kernel, block_rows=block_rows,
                )

            # Computed per pass, not once: a quarantine during an earlier
            # pass changes the registry, and this pass's partials must be
            # keyed by the pack set they actually scanned.
            job_key = self._job_key(
                self._reduce_tag(plan.method, plan.reduce, tag),
                plan.layout, gate, plan.qvec, plan.query.npix, windows,
                grid_tag=self._grid_tag(plan),
            )
            pass_keys.append(job_key)
            acc, counters, dt, pfc, pquar = self._run_stream_windows(
                plan.layout, exec_ds, windows, dispatch, job_key,
                keep_journal=True,
            )
            up, hi, ev = up + counters[0], hi + counters[1], ev + counters[2]
            elapsed += dt
            fc.retries += pfc.retries
            fc.speculative_windows += pfc.speculative_windows
            fc.quarantined_packs += pfc.quarantined_packs
            fc.resumed_windows += pfc.resumed_windows
            quar = tuple(sorted(set(quar) | set(pquar)))
            return acc

        clip_k = jnp.float32(self.clip_k)
        n_passes = 2
        s0, s1, s2, contrib, considered = run_pass(
            "moments", _moments_scan_sparse
        )
        if plan.reduce == "median":
            n_passes = 3
            lo, w, inv_w = _hist_operands(s0, s1, s2, nbins=self.median_bins)
            nb = self.median_bins
            (hist,) = run_pass(
                "hist",
                lambda *a, **kw: _hist_scan_sparse(*a, nbins=nb, **kw),
                lo, inv_w,
            )
            center, thresh = _median_operands(hist, s0, s1, s2, lo, w, clip_k)
        else:
            center, thresh = _clip_operands(s0, s1, s2, clip_k)
        coadd, depth = run_pass("clip", _clip_scan_sparse, center, thresh)
        # The whole job completed: every pass journal is now garbage.
        for key in pass_keys:
            self._retire_journal(key)
        quar = tuple(p for p in quar if gate[p].any())
        stats = JobStats(
            method=plan.method,
            files_considered=int(considered),
            files_contributing=int(contrib),
            packs_touched=plan.packs_touched,
            t_locate_s=plan.t_locate_s,
            t_map_reduce_s=elapsed,
            t_total_s=plan.t_locate_s + elapsed,
            dispatches=self.dispatch_count - d0,
            packs_gated=int(gate.any(axis=1).sum()),
            packs_scanned=n_passes * sum(w.budget for w in windows),
            scan_budget=max(w.budget for w in windows),
            windows=n_passes * len(windows),
            chunk_uploads=up,
            residency_hits=hi,
            residency_evictions=ev,
            matched_cache_builds=self.matched_builds - m_builds0,
            matched_cache_hits=hi if self._matched_mode() else 0,
            peak_resident_bytes=self._peak_resident_bytes(),
            retries=fc.retries,
            speculative_windows=fc.speculative_windows,
            quarantined_packs=fc.quarantined_packs,
            resumed_windows=fc.resumed_windows,
            partial=bool(quar),
            uncovered_packs=quar,
            requarantine_released=self._take_requarantine_released(),
            reduce=plan.reduce,
            reduce_passes=n_passes,
        )
        return CoaddResult(np.asarray(coadd), np.asarray(depth), stats)

    # ----- execution: one dispatch against resident data -----
    def execute(self, plan: CoaddPlan) -> CoaddResult:
        """One-dispatch query: device-resident packs + (P, cap) slot gate.

        With sparse execution on, the gate's padded pack-index vector is
        derived host-side and the jitted program gathers just those packs
        before scanning (`_coadd_scan_sparse`) — map work scales with
        `packs_gated` instead of the layout size, still in one dispatch.
        Under a device budget the query streams instead
        (`_execute_streaming`): windowed scans over budget-sized chunks.

        The eager path is traced (`repro.core.spans`): ``coadd.execute``
        holds ``prepare`` (``grid``, ``compact``, ``dispatch``), ``sync``
        (the one host sync) and ``fetch`` (the copy back); its transfers
        are `JobStats.h2d_bytes` / ``d2h_bytes``.
        """
        self._check_plan_psf(plan)
        if self.device_budget_bytes is not None:
            if plan.reduce != "mean":
                return self._execute_streaming_robust(plan)
            return self._execute_streaming(plan)
        with span("execute"):
            with span("execute.prepare"):
                exec_ds, _ = self.exec_dataset(plan.layout)
                gate = self._exec_gate(plan)
                m_builds0 = self.matched_builds
                fn, args, kwargs, sp, m_hits, h2d = self._eager_program(plan)
                scanned = sp.budget if sp is not None else exec_ds.n_packs
                window = kwargs.get("window")
                with span("execute.dispatch") as dispatch:
                    dispatch.set(windowed_packs=scanned if window else 0)
                    self.dispatch_count += 1
                    # Mean scans return a fifth result, the windowed pack
                    # steps (`_covered`); it is read only in windowed mode.
                    coadd, depth, contrib, considered, *steps = fn(*args,
                                                                   **kwargs)
            with span("execute.sync") as sync:
                coadd.block_until_ready()
                steps = steps if window else []
                counts = np.asarray(steps[0]) if steps else np.zeros(2)
                redo = not _covered(counts)
                if redo:
                    self.dispatch_count += 1
                    kwargs["window"] = None
                    coadd, depth, contrib, considered, _ = fn(*args, **kwargs)
                    coadd.block_until_ready()
                    counts = np.zeros(2)
            with span("execute.fetch") as fetch:
                d2h = _nbytes(coadd, depth, contrib, considered, *steps)
                fetch.set(d2h_bytes=d2h)
                t_mr = dispatch.seconds + sync.seconds
                stats = JobStats(
                    method=plan.method,
                    files_considered=int(considered),
                    files_contributing=int(contrib),
                    packs_touched=plan.packs_touched,
                    t_locate_s=plan.t_locate_s,
                    t_map_reduce_s=t_mr,
                    t_total_s=plan.t_locate_s + t_mr,
                    dispatches=1 + redo,
                    packs_gated=int(gate.any(axis=1).sum()),
                    packs_scanned=scanned,
                    scan_budget=scanned,
                    matched_cache_builds=self.matched_builds - m_builds0,
                    matched_cache_hits=m_hits,
                    peak_resident_bytes=self._peak_resident_bytes(),
                    reduce=plan.reduce,
                    h2d_bytes=h2d,
                    d2h_bytes=d2h,
                    windowed_packs=int(counts[0]),
                )
                return CoaddResult(np.asarray(coadd), np.asarray(depth), stats)

    def _eager_program(self, plan: CoaddPlan):
        """The jitted program and operands `execute` dispatches for a plan
        against the eager resident layout.

        Returns ``(fn, args, kwargs, sparse_index, matched_cache_hits,
        h2d_bytes)``; the call returns ``(coadd, depth, contributing,
        considered)``, and a mean scan also its windowed pack steps
        (`_covered`).  Uploads the layout (and builds the matched-pixel
        cache) on first use, exactly as the dispatch itself would need.
        The per-query operands are built under the ``coadd.execute.grid``
        and ``coadd.execute.compact`` spans; ``h2d_bytes`` is what they
        send up (48 bytes for a grid built on the device, `_plan_grids`).
        """
        ds = self.dataset(plan.layout)
        # Before the upload: a kernel-lane query whose frames cannot fit
        # VMEM is refused here, not after moving the archive to the device.
        block_rows = self._block_rows(plan.query, ds)
        dev = self.device_dataset(plan.layout)
        psf_kernels = self._device_psf_kernels(plan.layout)
        m_hits = 0
        if self._matched_mode():
            # §7: the dispatch reads pre-matched resident pixels; no bank
            # operand, no per-query convolution.
            dev, m_hits = self._matched_device_dataset(plan.layout, dev)
            psf_kernels = None
        with span("execute.grid",
                  on_device=int(plan.grid_sky is None)) as grid:
            grid_ra, grid_dec, h2d_grid = self._plan_grids(plan)
            grid.set(h2d_bytes=h2d_grid)
        robust = plan.reduce != "mean"
        with span("execute.compact") as compact:
            gate = self._exec_gate(plan)
            sp = self._sparse_index(gate)
            gate_dev = jnp.asarray(compact_gate(gate, sp) if sp is not None
                                   else gate)
            pack_idx = jnp.asarray(sp.pack_idx) if sp is not None else None
            qvec = jnp.asarray(plan.qvec)
            clip_k = jnp.float32(self.clip_k) if robust else None
            h2d_compact = _nbytes(gate_dev, pack_idx, qvec, clip_k)
            compact.set(h2d_bytes=h2d_compact)
        h2d = h2d_grid + h2d_compact
        operands = (dev.pixels, dev.wcs, dev.ints, dev.floats, psf_kernels)
        kwargs = dict(use_kernel=self.use_kernel, block_rows=block_rows)
        if robust:
            # Robust eager path: all passes fused into ONE jitted dispatch
            # (the in-program re-scan is what keeps clipped within the
            # perf-gate overhead budget vs the mean).
            kwargs.update(reduce=plan.reduce, median_bins=self.median_bins,
                          pack_idx=pack_idx)
            args = operands + (gate_dev, qvec, grid_ra, grid_dec, clip_k)
            return _robust_scan, args, kwargs, sp, m_hits, h2d
        kwargs["window"] = self._window_fit(
            plan, self.exec_dataset(plan.layout)[0], gate,
            psf_kernels is not None, dev.pixels)
        if sp is not None:
            args = operands + (pack_idx, gate_dev, qvec, grid_ra, grid_dec)
            return _coadd_scan_sparse, args, kwargs, sp, m_hits, h2d
        args = operands + (gate_dev, qvec, grid_ra, grid_dec)
        return _coadd_scan, args, kwargs, sp, m_hits, h2d

    def lower(self, plan: CoaddPlan) -> "jax.stages.Lowered":
        """The program `execute(plan)` dispatches, lowered but not run.

        ``lower(plan).compile().as_text()`` is what the device executes —
        e.g. whether the Pallas lane became a Mosaic ``tpu_custom_call``.
        Covers the eager path; a streaming engine dispatches one program
        per window and raises here.
        """
        if self.device_budget_bytes is not None:
            raise ValueError("lower() covers the eager path; this engine "
                             "streams under a device budget")
        self._check_plan_psf(plan)
        fn, args, kwargs, *_ = self._eager_program(plan)
        return fn.lower(*args, **kwargs)

    def _eager_resident_bytes(self) -> int:
        """Device bytes resident *outside* the ResidencyManager: the eager
        whole-layout uploads (`_device_cache`) and device kernel banks.
        Added to the manager's peak in JobStats so eager matched mode —
        raw pixels AND their matched copy simultaneously resident — reports
        the true single-host footprint, not just the managed half."""
        total = 0
        for dev in self._device_cache.values():
            total += int(dev.pixels.nbytes) + int(dev.wcs.nbytes)
            total += sum(int(v.nbytes) for v in dev.ints.values())
            total += sum(int(v.nbytes) for v in dev.floats.values())
        total += sum(int(b.nbytes) for b in self._psf_device.values())
        return total

    def _peak_resident_bytes(self) -> int:
        """The JobStats peak: managed high-water mark + unmanaged eager
        residents (zero under a device budget, where nothing is eager)."""
        return self.residency.peak_bytes + self._eager_resident_bytes()

    def _check_plan_psf(self, plan: CoaddPlan) -> None:
        """A plan built under one PSF target must not run under another.

        Kernel banks and the matched-pixel cache are keyed per target, so
        executing a stale plan on a retuned engine would silently stack
        images homogenized to a different PSF than the plan promised.
        """
        if plan.psf_target != self.match_psf_sigma:
            raise ValueError(
                f"plan was built with psf_target={plan.psf_target} but this "
                f"engine matches to {self.match_psf_sigma}; re-plan on the "
                "engine that will execute"
            )

    def run(self, query: CoaddQuery, method: str,
            use_bricks: bool = False, reduce: str = "mean") -> CoaddResult:
        """Plan + execute one query.

        With ``use_bricks=True`` (DESIGN.md §9) a brick-aligned query is
        served by mosaicking cached brick coadds — materializing any
        missing bricks inline — and an unaligned query falls back to the
        ordinary path transparently (its stats carry zero brick counters).
        ``reduce`` picks the stacking estimator (DESIGN.md §11): "mean",
        "clipped" (k-sigma-clipped mean), or "median" (two-round
        median+clip); bricks are materialized and cached per estimator.
        """
        if use_bricks:
            res = self._run_bricks(query, method, reduce)
            if res is not None:
                return res
        return self.execute(self.plan(query, method, reduce))

    # ----- brick-tessellated materialized coadds (DESIGN.md §9) -----
    @property
    def brick_grid(self) -> BrickGrid:
        """The survey's brick tessellation (built lazily, fixed per engine)."""
        if self._brick_grid is None:
            self._brick_grid = BrickGrid.for_survey(
                self.survey.config, self.brick_deg, self.brick_npix
            )
        return self._brick_grid

    def _brick_key(self, band: str, row: int, col: int,
                   reduce: str = "mean") -> Tuple:
        """BrickStore identity of one materialized (brick, band) cell.

        Carries `_psf_state()` so a retuned engine misses and
        re-materializes instead of mosaicking tiles homogenized to a
        different target — staleness by key, the same contract as every
        other derived-residency cache.  Robust estimators extend the key
        (with their clip knobs — retuning k or the bin count must miss);
        mean keys stay exactly the pre-§11 shape so existing stores and
        spills remain valid.
        """
        key = ("brick", band, row, col, self._psf_state())
        if reduce != "mean":
            key += (reduce, self.clip_k, self.median_bins)
        return key

    def _brick_plan(self, band: str, row: int, col: int,
                    method: str, reduce: str = "mean") -> CoaddPlan:
        """The materialization plan for one brick: a normal planned query
        whose output grid is overridden onto the global lattice tile."""
        plan = self.plan(
            self.brick_grid.brick_query(row, col, band), method, reduce
        )
        plan.grid_sky = self.brick_grid.brick_sky(row, col)
        return plan

    def result_key(self, plan: CoaddPlan) -> str:
        """Serving-cache identity of one plan's result (DESIGN.md §10).

        The plan's value fingerprint (layout, grid, gate bytes, qvec bytes
        — `CoaddPlan.fingerprint`) joined with the engine state that also
        determines the pixels: the live PSF state (a retuned engine must
        miss, the same contract as every derived-residency cache) and the
        execution knobs that pick the program family (kernel vs XLA, sparse
        gather, streaming partition — float summation order differs across
        them, so bits may too).  Contract: equal keys ⇒ bitwise-equal
        coadds, so a serving layer may answer the second request from the
        first's cached output.
        """
        key = (
            f"{plan.fingerprint}|{self._psf_state()}"
            f"|k{int(self.use_kernel)}|s{int(self.sparse)}"
            f"|b{self.device_budget_bytes}"
        )
        if plan.reduce != "mean":
            # Robust knobs are engine state, not plan state — two engines
            # with different clip-k must not share a cached clipped stack.
            key += f"|ck{self.clip_k}|mb{self.median_bins}"
        return key

    def warm_brick_cover(self, query: CoaddQuery,
                         reduce: str = "mean") -> Optional[BrickCover]:
        """This query's brick cover iff *every* covered tile is stored.

        The serving front end routes such queries straight to the
        one-dispatch mosaic path (`run(use_bricks=True)`) — a guaranteed
        warm serve, never an inline materialization surprise under load.
        None when the query is unaligned or any tile is cold; the caller
        counts that miss into the `bricks_missed` popularity signal that
        decides what to materialize next (DESIGN.md §9/§10).
        """
        cover = self.brick_grid.decompose(query)
        if cover is None:
            return None
        store = self.brick_store
        if all(store.contains(self._brick_key(query.band, r, c, reduce))
               for r, c in cover.bricks):
            return cover
        return None

    def run_window(self, query: CoaddQuery, method: str,
                   reduce: str = "mean") -> CoaddResult:
        """The brick-free baseline for a brick-aligned query: one fresh
        scan onto the lattice-window grid.  This is the path
        `run(use_bricks=True)` must match bitwise — same lattice pixels,
        same gate semantics, no bricks consulted.  Raises on queries that
        do not decompose (use plain `run` for those)."""
        cover = self.brick_grid.decompose(query)
        if cover is None:
            raise ValueError(
                "query is not brick-aligned; run_window only serves "
                "lattice-window queries (see BrickGrid.window_query)"
            )
        plan = self.plan(query, method, reduce)
        plan.grid_sky = self.brick_grid.window_sky(
            cover.r0, cover.r1, cover.c0, cover.c1
        )
        return self.execute(plan)

    def _run_bricks(self, query: CoaddQuery, method: str,
                    reduce: str = "mean") -> Optional[CoaddResult]:
        """Serve a brick-aligned query from the BrickStore, or None.

        Decomposes the query into its brick cover, fetches every covered
        tile (device tier preferred, host-spill re-upload otherwise),
        freshly materializes the misses inline — each a normal `execute`
        under the full §8 fault domain, stored for the next query — and
        merges the tiles with one jitted weighted-sum mosaic dispatch.
        """
        cover = self.brick_grid.decompose(query)
        if cover is None:
            return None
        t0 = time.perf_counter()
        store = self.brick_store
        b = self.brick_npix
        d0 = self.dispatch_count
        hits = spills = 0
        tiles: List = []
        covs: List = []
        offsets: List[Tuple[int, int]] = []
        metas: List[Optional[BrickMeta]] = []
        missing: List[int] = []
        for i, (r, c) in enumerate(cover.bricks):
            offsets.append(((r - cover.r0) * b, (c - cover.c0) * b))
            got = store.fetch(self._brick_key(query.band, r, c, reduce))
            if got is None:
                missing.append(i)
                tiles.append(None)
                covs.append(None)
                metas.append(None)
                continue
            coadd_dev, depth_dev, meta, tier = got
            if tier == "device":
                hits += 1
            else:
                spills += 1
            tiles.append(coadd_dev)
            covs.append(depth_dev)
            metas.append(meta)
        t_fetch = time.perf_counter() - t0
        # The residual: bricks nobody materialized yet.  Each miss pays one
        # fresh streaming scan now and is cached for every query after.
        residual = JobStats("", 0, 0, 0, 0.0, 0.0, 0.0, dispatches=0)
        for i in missing:
            r, c = cover.bricks[i]
            res = self.execute(
                self._brick_plan(query.band, r, c, method, reduce)
            )
            meta = BrickMeta(
                partial=res.stats.partial,
                uncovered_packs=res.stats.uncovered_packs,
                files_considered=res.stats.files_considered,
                files_contributing=res.stats.files_contributing,
            )
            coadd_dev, depth_dev = store.put(
                self._brick_key(query.band, r, c, reduce),
                res.coadd, res.depth, meta,
            )
            tiles[i] = coadd_dev
            covs[i] = depth_dev
            metas[i] = meta
            s = res.stats
            residual.t_locate_s += s.t_locate_s
            residual.t_map_reduce_s += s.t_map_reduce_s
            residual.packs_touched += s.packs_touched
            residual.packs_gated += s.packs_gated
            residual.packs_scanned += s.packs_scanned
            residual.scan_budget = max(residual.scan_budget, s.scan_budget)
            residual.windows += s.windows
            residual.chunk_uploads += s.chunk_uploads
            residual.residency_hits += s.residency_hits
            residual.residency_evictions += s.residency_evictions
            residual.matched_cache_builds += s.matched_cache_builds
            residual.matched_cache_hits += s.matched_cache_hits
            residual.retries += s.retries
            residual.speculative_windows += s.speculative_windows
            residual.quarantined_packs += s.quarantined_packs
            residual.resumed_windows += s.resumed_windows
            residual.reduce_passes = max(residual.reduce_passes,
                                         s.reduce_passes)
        t1 = time.perf_counter()
        self.dispatch_count += 1
        coadd, depth = _mosaic_bricks(
            jnp.stack(tiles),
            jnp.stack(covs),
            jnp.asarray(np.array(offsets, np.int32)),
            query.npix,
            use_kernel=self.use_kernel,
        )
        coadd.block_until_ready()
        t2 = time.perf_counter()
        uncovered = sorted(
            {p for m in metas for p in m.uncovered_packs}
        )
        stats = JobStats(
            method=method,
            files_considered=sum(m.files_considered for m in metas),
            files_contributing=sum(m.files_contributing for m in metas),
            packs_touched=residual.packs_touched,
            t_locate_s=t_fetch + residual.t_locate_s,
            t_map_reduce_s=residual.t_map_reduce_s + (t2 - t1),
            t_total_s=(t2 - t0),
            dispatches=self.dispatch_count - d0,
            packs_gated=residual.packs_gated,
            packs_scanned=residual.packs_scanned,
            scan_budget=residual.scan_budget,
            windows=residual.windows,
            chunk_uploads=residual.chunk_uploads,
            residency_hits=residual.residency_hits,
            residency_evictions=residual.residency_evictions,
            matched_cache_builds=residual.matched_cache_builds,
            matched_cache_hits=residual.matched_cache_hits,
            peak_resident_bytes=self._peak_resident_bytes(),
            retries=residual.retries,
            speculative_windows=residual.speculative_windows,
            quarantined_packs=residual.quarantined_packs,
            resumed_windows=residual.resumed_windows,
            partial=any(m.partial for m in metas),
            uncovered_packs=tuple(uncovered),
            bricks_hit=hits,
            bricks_missed=len(missing),
            bricks_spilled=spills,
            residual_packs_scanned=residual.packs_scanned,
            reduce=reduce,
            reduce_passes=residual.reduce_passes if missing else 1,
        )
        return CoaddResult(np.asarray(coadd), np.asarray(depth), stats)

    def materialize_bricks(
        self,
        bands: Sequence[str] = ("r",),
        region: Optional[Tuple[Tuple[float, float], Tuple[float, float]]] = None,
        method: str = "sql_structured",
        reduce: str = "mean",
    ) -> MaterializeReport:
        """Batch-materialize the (brick, band) lattice into the BrickStore.

        Every cell is one normal planned+executed brick query driven
        through the streaming executors under the §8 fault domain, then
        journaled by its presence in the store: a killed job re-issued with
        the same arguments skips finished bricks and resumes the in-flight
        one from its window journal.  ``region=(ra_bounds, dec_bounds)``
        restricts to intersecting cells; bricks already materialized (same
        PSF state) are skipped.
        """
        grid = self.brick_grid
        cells = grid.bricks(region)
        tasks = [
            BrickTask(band=band, row=r, col=c)
            for band in bands for (r, c) in cells
        ]
        tracker = MaterializeTracker(
            max_attempts=self.fault_max_attempts,
            backoff_s=self.fault_backoff_s,
        )

        def is_done(task: BrickTask) -> bool:
            return self.brick_store.contains(
                self._brick_key(task.band, task.row, task.col, reduce)
            )

        def run_one(task: BrickTask) -> None:
            res = self.execute(
                self._brick_plan(task.band, task.row, task.col, method,
                                 reduce)
            )
            self.brick_store.put(
                self._brick_key(task.band, task.row, task.col, reduce),
                res.coadd,
                res.depth,
                BrickMeta(
                    partial=res.stats.partial,
                    uncovered_packs=res.stats.uncovered_packs,
                    files_considered=res.stats.files_considered,
                    files_contributing=res.stats.files_contributing,
                ),
            )
            task.status = "partial" if res.stats.partial else "done"
            task.packs_scanned = res.stats.packs_scanned
            task.retries = res.stats.retries
            task.resumed_windows = res.stats.resumed_windows

        return MaterializeReport(tracker.run(tasks, is_done, run_one))

    # ----- batched multi-query jobs (paper Fig. 5) -----
    def run_batch(
        self, queries: Sequence[CoaddQuery], method: str,
        reduce: str = "mean",
    ) -> List[CoaddResult]:
        """K same-method queries as ONE jitted dispatch over one layout."""
        queries = list(queries)
        if not queries:
            return []
        return self.execute_batch(
            [self.plan(q, method, reduce) for q in queries]
        )

    def execute_batch(self, plans: Sequence[CoaddPlan]) -> List[CoaddResult]:
        """Stacked plans -> one vmapped scan dispatch -> per-query results.

        Sparse batches compact against the *union* of the gates' packs
        (`union_sparse_index`), each query's compacted gate re-selecting its
        own slots — K queries remain ONE dispatch over one gathered layout.
        """
        plans = list(plans)
        for p in plans:
            self._check_plan_psf(p)
        gates, qvecs = stack_plans(plans)
        layout = plans[0].layout
        ds = self.dataset(layout)
        exec_ds, remap = self.exec_dataset(layout)
        if remap is not None:
            gates = np.stack([remap.apply(g) for g in gates])
        grids = [self._plan_grids(p) for p in plans]
        grids_ra = jnp.stack([g[0] for g in grids])
        grids_dec = jnp.stack([g[1] for g in grids])
        block_rows = self._block_rows(plans[0].query, ds)
        if self.device_budget_bytes is not None:
            return self._execute_batch_streaming(
                plans, exec_ds, gates, qvecs, grids_ra, grids_dec, block_rows
            )
        dev = self.device_dataset(layout)
        psf_kernels = self._device_psf_kernels(layout)
        m_builds0, m_hits = self.matched_builds, 0
        if self._matched_mode():
            dev, m_hits = self._matched_device_dataset(layout, dev)
            psf_kernels = None
        sp = self._sparse_index(gates)
        t1 = time.perf_counter()
        self.dispatch_count += 1
        counts = np.zeros((len(plans), 2), np.int64)
        n_dispatches = 1
        if plans[0].reduce != "mean":
            # Robust batch, still ONE dispatch: the fused per-query passes
            # vmap over the stacked gates/grids (stack_plans guarantees one
            # shared reduce for the whole batch).
            gates_dev = (jnp.asarray(compact_gates(gates, sp))
                         if sp is not None else jnp.asarray(gates))
            pack_idx = jnp.asarray(sp.pack_idx) if sp is not None else None
            coadds, depths, contribs, considered = _robust_scan_batch(
                dev.pixels,
                dev.wcs,
                dev.ints,
                dev.floats,
                psf_kernels,
                gates_dev,
                jnp.asarray(qvecs),
                grids_ra,
                grids_dec,
                jnp.float32(self.clip_k),
                use_kernel=self.use_kernel,
                block_rows=block_rows,
                reduce=plans[0].reduce,
                median_bins=self.median_bins,
                pack_idx=pack_idx,
            )
        else:
            window = self._batch_window_fit(plans, exec_ds, gates,
                                            psf_kernels is not None,
                                            dev.pixels)
            if sp is not None:
                fn = partial(_coadd_scan_batch_sparse,
                             pack_idx=jnp.asarray(sp.pack_idx),
                             gates=jnp.asarray(compact_gates(gates, sp)))
            else:
                fn = partial(_coadd_scan_batch, gates=jnp.asarray(gates))
            fn = partial(fn, dev.pixels, dev.wcs, dev.ints, dev.floats,
                         psf_kernels, qvecs=jnp.asarray(qvecs),
                         grids_ra=grids_ra, grids_dec=grids_dec,
                         use_kernel=self.use_kernel, block_rows=block_rows)
            coadds, depths, contribs, considered, steps = fn(window=window)
            if window is not None:
                counts = np.asarray(steps)
                if not _covered(counts):
                    n_dispatches += 1
                    self.dispatch_count += 1
                    coadds, depths, contribs, considered, _ = fn(window=None)
                    counts = np.zeros_like(counts)
        coadds.block_until_ready()
        t2 = time.perf_counter()
        contribs = np.asarray(contribs)
        considered = np.asarray(considered)
        scanned = sp.budget if sp is not None else exec_ds.n_packs
        results = []
        for i, p in enumerate(plans):
            # One dispatch — and one wall-clock interval — serves the whole
            # batch; attribute both to the first result so summing stats
            # across the batch stays honest.
            t_mr = (t2 - t1) if i == 0 else 0.0
            stats = JobStats(
                method=p.method,
                files_considered=int(considered[i]),
                files_contributing=int(contribs[i]),
                packs_touched=p.packs_touched,
                t_locate_s=p.t_locate_s,
                t_map_reduce_s=t_mr,
                t_total_s=p.t_locate_s + t_mr,
                dispatches=n_dispatches if i == 0 else 0,
                packs_gated=int(gates[i].any(axis=1).sum()),
                packs_scanned=scanned if i == 0 else 0,
                scan_budget=scanned,
                matched_cache_builds=(self.matched_builds - m_builds0)
                if i == 0 else 0,
                matched_cache_hits=m_hits if i == 0 else 0,
                peak_resident_bytes=self._peak_resident_bytes(),
                reduce=p.reduce,
                windowed_packs=int(counts[i, 0]),
            )
            results.append(
                CoaddResult(np.asarray(coadds[i]), np.asarray(depths[i]), stats)
            )
        return results

    def _execute_batch_streaming(
        self, plans, exec_ds, gates, qvecs, grids_ra, grids_dec, block_rows,
        gather_only=False,
    ) -> List[CoaddResult]:
        """Windowed batch under a device budget (DESIGN.md §6).

        Windows come from the *union* of the K gates (one gathered chunk
        serves the whole batch, as in §5's union compaction); each window
        is one vmapped dispatch, partials accumulate per query, and the
        host syncs once at the end.
        """
        layout = plans[0].layout
        if plans[0].reduce != "mean" and gates.any():
            return self._execute_batch_streaming_robust(
                plans, exec_ds, gates, qvecs, grids_ra, grids_dec, block_rows
            )
        if not gates.any():
            # Empty union: every query selected nothing — answer zeros
            # without a window schedule (same contract as the single path).
            res = [self._empty_streaming_result(p) for p in plans]
            for p, r in zip(plans, res):
                r.stats.reduce = p.reduce
            return res
        union_any = gates.any(axis=0).any(axis=1)
        windows = self._stream_windows(exec_ds, union_any)
        qvecs_j = jnp.asarray(qvecs)
        m_builds0, d0 = self.matched_builds, self.dispatch_count

        def dispatch(dev, kern, win, dropped):
            g = gates
            if dropped:
                g = gates.copy()
                g[:, sorted(dropped)] = False
            self.dispatch_count += 1
            return _coadd_scan_batch_sparse(
                dev.pixels,
                dev.wcs,
                dev.ints,
                dev.floats,
                kern,
                jnp.asarray(win.pack_idx),
                jnp.asarray(compact_window_gates(g, win)),
                qvecs_j,
                grids_ra,
                grids_dec,
                use_kernel=self.use_kernel,
                block_rows=block_rows,
                window=None if gather_only else self._batch_window_fit(
                    plans, exec_ds, gates, kern is not None, dev.pixels),
            )

        job_key = self._job_key(
            "batch:" + plans[0].method, layout, gates, qvecs, plans[0].npix,
            windows,
            grid_tag="|".join(self._grid_tag(p) for p in plans)
            + ("|gather" if gather_only else ""),
        )
        (coadds, depths, contribs, considered, steps), counters, elapsed, \
            fc, quar = self._run_stream_windows(layout, exec_ds, windows,
                                                dispatch, job_key)
        steps = np.asarray(steps)
        if not _covered(steps):
            return self._execute_batch_streaming(
                plans, exec_ds, gates, qvecs, grids_ra, grids_dec, block_rows,
                gather_only=True)
        uploads, hits, evictions = counters
        # Same coverage honesty as the single path: uncovered = quarantined
        # AND opened by at least one of the batch's gates.
        union_gate = gates.any(axis=0)
        quar = tuple(p for p in quar if union_gate[p].any())
        released = self._take_requarantine_released()
        contribs = np.asarray(contribs)
        considered = np.asarray(considered)
        scanned = sum(w.budget for w in windows)
        results = []
        for i, p in enumerate(plans):
            t_mr = elapsed if i == 0 else 0.0
            stats = JobStats(
                method=p.method,
                files_considered=int(considered[i]),
                files_contributing=int(contribs[i]),
                packs_touched=p.packs_touched,
                t_locate_s=p.t_locate_s,
                t_map_reduce_s=t_mr,
                t_total_s=p.t_locate_s + t_mr,
                dispatches=(self.dispatch_count - d0) if i == 0 else 0,
                packs_gated=int(gates[i].any(axis=1).sum()),
                packs_scanned=scanned if i == 0 else 0,
                scan_budget=max(w.budget for w in windows),
                windowed_packs=int(steps[i, 0]),
                windows=len(windows),
                chunk_uploads=uploads if i == 0 else 0,
                residency_hits=hits if i == 0 else 0,
                residency_evictions=evictions if i == 0 else 0,
                matched_cache_builds=(self.matched_builds - m_builds0)
                if i == 0 else 0,
                matched_cache_hits=hits
                if (i == 0 and self._matched_mode()) else 0,
                peak_resident_bytes=self._peak_resident_bytes(),
                # Fault counters are additive -> first result; quarantine
                # coverage loss affects every query in the batch -> all.
                retries=fc.retries if i == 0 else 0,
                speculative_windows=fc.speculative_windows if i == 0 else 0,
                quarantined_packs=fc.quarantined_packs if i == 0 else 0,
                resumed_windows=fc.resumed_windows if i == 0 else 0,
                partial=bool(quar),
                uncovered_packs=quar,
                requarantine_released=released if i == 0 else 0,
            )
            results.append(
                CoaddResult(np.asarray(coadds[i]), np.asarray(depths[i]), stats)
            )
        return results

    def _execute_batch_streaming_robust(
        self, plans, exec_ds, gates, qvecs, grids_ra, grids_dec, block_rows
    ) -> List[CoaddResult]:
        """Robust batch under a device budget: the §11 multi-pass contract
        over the union window schedule.  Same journaling/retirement rules
        as `_execute_streaming_robust`, vmapped over the batch's queries
        (per-query clip operands ride the batch axis between passes)."""
        layout = plans[0].layout
        reduce = plans[0].reduce
        union_any = gates.any(axis=0).any(axis=1)
        windows = self._stream_windows(exec_ds, union_any)
        qvecs_j = jnp.asarray(qvecs)
        m_builds0, d0 = self.matched_builds, self.dispatch_count
        up = hi = ev = 0
        elapsed = 0.0
        fc = FaultCounters()
        pass_keys: List[str] = []
        quar: Tuple[int, ...] = ()

        def run_pass(tag: str, pass_fn, *extra):
            nonlocal up, hi, ev, elapsed, quar

            def dispatch(dev, kern, win, dropped):
                g = gates
                if dropped:
                    g = gates.copy()
                    g[:, sorted(dropped)] = False
                self.dispatch_count += 1
                return pass_fn(
                    dev.pixels, dev.wcs, dev.ints, dev.floats, kern,
                    jnp.asarray(win.pack_idx),
                    jnp.asarray(compact_window_gates(g, win)),
                    qvecs_j, grids_ra, grids_dec, *extra,
                    use_kernel=self.use_kernel, block_rows=block_rows,
                )

            job_key = self._job_key(
                "batch:" + self._reduce_tag(plans[0].method, reduce, tag),
                layout, gates, qvecs, plans[0].npix, windows,
                grid_tag="|".join(self._grid_tag(p) for p in plans),
            )
            pass_keys.append(job_key)
            acc, counters, dt, pfc, pquar = self._run_stream_windows(
                layout, exec_ds, windows, dispatch, job_key,
                keep_journal=True,
            )
            up, hi, ev = up + counters[0], hi + counters[1], ev + counters[2]
            elapsed += dt
            fc.retries += pfc.retries
            fc.speculative_windows += pfc.speculative_windows
            fc.quarantined_packs += pfc.quarantined_packs
            fc.resumed_windows += pfc.resumed_windows
            quar = tuple(sorted(set(quar) | set(pquar)))
            return acc

        clip_k = jnp.float32(self.clip_k)
        n_passes = 2
        s0, s1, s2, contribs, considered = run_pass(
            "moments", _moments_scan_batch_sparse
        )
        if reduce == "median":
            n_passes = 3
            nb = self.median_bins
            los, ws, inv_ws = _hist_operands(s0, s1, s2, nbins=nb)
            (hists,) = run_pass(
                "hist",
                lambda *a, **kw: _hist_scan_batch_sparse(*a, nbins=nb, **kw),
                los, inv_ws,
            )
            centers, threshs = jax.vmap(
                _median_operands, in_axes=(0, 0, 0, 0, 0, 0, None)
            )(hists, s0, s1, s2, los, ws, clip_k)
        else:
            centers, threshs = _clip_operands(s0, s1, s2, clip_k)
        coadds, depths = run_pass(
            "clip", _clip_scan_batch_sparse, centers, threshs
        )
        for key in pass_keys:
            self._retire_journal(key)
        union_gate = gates.any(axis=0)
        quar = tuple(p for p in quar if union_gate[p].any())
        released = self._take_requarantine_released()
        contribs = np.asarray(contribs)
        considered = np.asarray(considered)
        scanned = n_passes * sum(w.budget for w in windows)
        results = []
        for i, p in enumerate(plans):
            t_mr = elapsed if i == 0 else 0.0
            stats = JobStats(
                method=p.method,
                files_considered=int(considered[i]),
                files_contributing=int(contribs[i]),
                packs_touched=p.packs_touched,
                t_locate_s=p.t_locate_s,
                t_map_reduce_s=t_mr,
                t_total_s=p.t_locate_s + t_mr,
                dispatches=(self.dispatch_count - d0) if i == 0 else 0,
                packs_gated=int(gates[i].any(axis=1).sum()),
                packs_scanned=scanned if i == 0 else 0,
                scan_budget=max(w.budget for w in windows),
                windows=n_passes * len(windows),
                chunk_uploads=up if i == 0 else 0,
                residency_hits=hi if i == 0 else 0,
                residency_evictions=ev if i == 0 else 0,
                matched_cache_builds=(self.matched_builds - m_builds0)
                if i == 0 else 0,
                matched_cache_hits=hi
                if (i == 0 and self._matched_mode()) else 0,
                peak_resident_bytes=self._peak_resident_bytes(),
                retries=fc.retries if i == 0 else 0,
                speculative_windows=fc.speculative_windows if i == 0 else 0,
                quarantined_packs=fc.quarantined_packs if i == 0 else 0,
                resumed_windows=fc.resumed_windows if i == 0 else 0,
                partial=bool(quar),
                uncovered_packs=quar,
                requarantine_released=released if i == 0 else 0,
                reduce=p.reduce,
                reduce_passes=n_passes,
            )
            results.append(
                CoaddResult(np.asarray(coadds[i]), np.asarray(depths[i]), stats)
            )
        return results

    # ----- distributed (production) path -----
    def run_distributed(
        self,
        queries: Sequence[CoaddQuery],
        mesh: Mesh,
        data_axes: Tuple[str, ...] = ("data",),
        model_axis: Optional[str] = "model",
    ) -> List[CoaddResult]:
        """Multi-query MapReduce over a device mesh.

        The structured layout is sharded over the data axes ONCE
        (`mesh_dataset`; cached per mesh) so repeat jobs move zero pixel
        bytes; each job ships per-query flat slot gates (exact spatial-index
        selection, i.e. the paper's best method), every device maps the
        *gated* entries of its resident slab (per-shard local compaction —
        dense fallback maps the whole slab), and reduction is psum over data
        axes + reduce-scatter of output rows over the model axis
        (`reducer.py`).
        """
        queries = list(queries)
        if not queries:
            return []
        npix = queries[0].npix
        if any(q.npix != npix for q in queries):
            raise ValueError("all queries in one job must share npix")
        model_size = mesh.shape[model_axis] if model_axis else 1
        if npix % max(model_size, 1):
            raise ValueError(f"npix={npix} must divide by model axis {model_size}")

        # Images are sharded over *every* mesh axis (map work on all devices);
        # the reduction then psums over the data axes and reduce-scatters over
        # the model axis, leaving each model shard a band of the coadd.
        shard_axes = tuple(data_axes) + ((model_axis,) if model_axis else ())
        ds = self.dataset("structured")
        t0 = time.perf_counter()
        id_sets = [self.sql.select(q) for q in queries]
        nonempty = [i for i in id_sets if len(i)]
        all_ids = (
            np.unique(np.concatenate(nonempty)) if nonempty
            else np.array([], np.int64)
        )
        t_locate = time.perf_counter() - t0
        if len(all_ids) == 0:
            # Nothing overlaps any query: answer with zero coadds instead of
            # padding a phantom image through the map stage.
            stats = lambda: JobStats(  # noqa: E731
                method="distributed_sql_structured",
                files_considered=0,
                files_contributing=0,
                packs_touched=0,
                t_locate_s=t_locate,
                t_map_reduce_s=0.0,
                t_total_s=t_locate,
                dispatches=0,
            )
            return [
                CoaddResult(
                    np.zeros((npix, npix), np.float32),
                    np.zeros((npix, npix), np.float32),
                    stats(),
                )
                for _ in queries
            ]

        n_shards = shard_count(mesh, shard_axes)
        exec_ds, _ = self.exec_dataset("structured")
        pad_to = exec_ds.flat_len(n_shards)
        t0 = time.perf_counter()
        # Per-job host->mesh traffic: gates + qvecs + the grids' WCS
        # vectors (the grids are built on the device). No pixels.
        gates = np.stack(
            [ds.flat_slot_mask(ids, pad_to=pad_to) for ids in id_sets]
        )
        t_locate += time.perf_counter() - t0
        block_rows = self._block_rows(queries[0], ds)
        grids = jnp.stack([
            jnp.stack(mapper.query_grid(
                mapper.grid_vector(q.grid_wcs_vector()), q.npix))
            for q in queries])
        qvecs = np.stack([_query_vec(q) for q in queries])  # (nq, 7)
        nq = len(queries)

        # Flat-axis residency windows (DESIGN.md §6).  With no budget the
        # whole archive shards once ([0, M) via the mesh_dataset cache, a
        # pixel upload outside the locate window so first-job and repeat-job
        # stats stay comparable).  Under a per-device budget the flat axis
        # streams in shard-aligned windows sized so two per-shard slabs —
        # scanning and uploading — fit the budget (double buffering).
        img_bytes = max(
            (exec_ds.pack_nbytes() + self._bank_pack_nbytes("structured"))
            // max(exec_ds.capacity, 1),
            1,
        )
        if self.device_budget_bytes is None:
            flat_windows = [(0, pad_to)]
        else:
            per_shard = max(1, int(self.device_budget_bytes // (2 * img_bytes)))
            win_flat = min(pad_to, per_shard * n_shards)
            flat_windows = [
                (a, min(a + win_flat, pad_to))
                for a in range(0, pad_to, win_flat)
            ]
            if self.sparse:
                union = gates.any(axis=0)
                flat_windows = [
                    (a, b) for a, b in flat_windows if union[a:b].any()
                ] or flat_windows[:1]

        meta_keys_i = tuple(sorted(exec_ds.ints.keys()))
        meta_keys_f = tuple(sorted(exec_ds.floats.keys()))
        use_kernel = self.use_kernel
        in_spec = P(shard_axes)
        out_rows = P(None, model_axis) if model_axis else P(None)

        def window_job(mds, gates_exec, local_idx, budgets, tile, local_len):
            """One shard_map dispatch over one resident flat window."""
            idx_t = (
                () if local_idx is None
                else (jnp.asarray(local_idx.reshape(-1)),)
            )
            bud_t = () if local_idx is None else (jnp.asarray(budgets),)
            kern_t = () if mds.psf_kernels is None else (mds.psf_kernels,)

            def job(px, wv, ints_flat, floats_flat, kern_t, idx_t, bud_t,
                    gates, qvecs, grids):
                ints = dict(zip(meta_keys_i, ints_flat))
                floats = dict(zip(meta_keys_f, floats_flat))
                kern = kern_t[0] if kern_t else None
                npix_q = grids.shape[-1]

                def collect(c, d):
                    return reducer.reduce_collective(
                        c, d, axis_name=data_axes, scatter_axis_name=model_axis
                    )

                if not idx_t:
                    # Dense fallback: map the whole resident slab.
                    def one_query(gate, qvec, grid):
                        accept = _accept_from_meta(ints, floats, qvec) & gate
                        return collect(*_map_reduce(
                            px, wv, accept, grid[0], grid[1], kern,
                            use_kernel, block_rows,
                        ))

                    return jax.vmap(one_query)(gates, qvecs, grids)

                # Local compaction with per-shard budgets (DESIGN.md §5/§6):
                # the gather+map runs in `tile`-sized steps and each shard's
                # fori_loop stops at its OWN bucketed budget — a quiet shard
                # gathers and maps only its own gated entries, not the
                # busiest shard's worth.  The psum/scatter collectives sit
                # after the loop, so divergent trip counts never desync the
                # collective schedule.
                idx = idx_t[0]            # (shared_budget,) local indices
                my_budget = bud_t[0][0]   # () this shard's own bucket

                def tile_step(t, acc):
                    c_acc, d_acc = acc
                    sl = jax.lax.dynamic_slice(idx, (t * tile,), (tile,))
                    px_t = jnp.take(px, sl, axis=0)
                    wv_t = jnp.take(wv, sl, axis=0)
                    ints_t = {k: jnp.take(v, sl, axis=0)
                              for k, v in ints.items()}
                    floats_t = {k: jnp.take(v, sl, axis=0)
                                for k, v in floats.items()}
                    kern_tile = (
                        None if kern is None else jnp.take(kern, sl, axis=0)
                    )
                    gates_t = jax.lax.dynamic_slice(
                        gates, (0, t * tile), (nq, tile)
                    )

                    def one_query(gate, qvec, grid):
                        accept = _accept_from_meta(ints_t, floats_t, qvec) & gate
                        return _map_reduce(
                            px_t, wv_t, accept, grid[0], grid[1], kern_tile,
                            use_kernel, block_rows,
                        )

                    c, d = jax.vmap(one_query)(gates_t, qvecs, grids)
                    return (c_acc + c, d_acc + d)

                init = (
                    jnp.zeros((nq, npix_q, npix_q), jnp.float32),
                    jnp.zeros((nq, npix_q, npix_q), jnp.float32),
                )
                n_tiles = (my_budget + tile - 1) // tile
                c, d = jax.lax.fori_loop(0, n_tiles, tile_step, init)
                return jax.vmap(collect)(c, d)

            # vmap-of-psum fails the VMA checker (psum_invariant rejects
            # axis_index_groups), so the check is off.
            shard = jax.shard_map(
                job,
                mesh=mesh,
                in_specs=(
                    in_spec,
                    in_spec,
                    (in_spec,) * len(meta_keys_i),
                    (in_spec,) * len(meta_keys_f),
                    (in_spec,) * len(kern_t),
                    (in_spec,) * len(idx_t),
                    (in_spec,) * len(bud_t),
                    P(None, shard_axes),
                    P(None),
                    P(None),
                ),
                out_specs=(out_rows, out_rows),
                check_vma=False,
            )
            self.dispatch_count += 1
            return shard(
                mds.pixels,
                mds.wcs,
                tuple(mds.ints[k] for k in meta_keys_i),
                tuple(mds.floats[k] for k in meta_keys_f),
                kern_t,
                idx_t,
                bud_t,
                jnp.asarray(gates_exec),
                jnp.asarray(qvecs),
                grids,
            )

        def mesh_window(a: int, b: int) -> MeshResidentDataset:
            if self.device_budget_bytes is None:
                return self.mesh_dataset("structured", mesh, shard_axes)
            key = ("mesh", "structured", mesh, tuple(shard_axes), a, b,
                   self._psf_state())

            def build():
                self.mesh_upload_count += 1
                return exec_ds.to_mesh_window(
                    mesh, tuple(shard_axes), a, b,
                    psf_kernels=self.psf_kernel_bank("structured"),
                )

            # Budget accounting is per device: each shard holds 1/n_shards
            # of the window.
            return self.residency.acquire(
                key, (b - a) // n_shards * img_bytes, build
            )

        up0, hit0, ev0 = (self.residency.uploads, self.residency.hits,
                          self.residency.evictions)
        # Eager path: the one-time whole-layout shard (a pixel upload, not
        # job init) stays outside the timed window so first-job and
        # repeat-job stats are comparable — mirroring how execute() leaves
        # device_dataset untimed.  Streaming windows upload *inside* it:
        # the overlapped transfer is exactly what time-to-first-coadd
        # measures.
        if self.device_budget_bytes is None:
            mds = mesh_window(*flat_windows[0])
        t1 = time.perf_counter()
        if self.device_budget_bytes is not None:
            mds = mesh_window(*flat_windows[0])
        coadds = depths = None
        packs_scanned = 0
        scan_budget_max = 0
        shards_touched = np.zeros((nq,), np.int64)
        for i, (a, b) in enumerate(flat_windows):
            local_len = (b - a) // n_shards
            gates_w = gates[:, a:b]
            # Per-shard local compaction (DESIGN.md §5): each shard gathers
            # only the slab entries some query in the job selected; shipped
            # per-query gates are compacted to the same local coordinates,
            # padding masked False.
            local_idx = budgets = None
            tile = local_len
            budget_w = local_len
            if self.sparse:
                local_idx, pad_mask, budget, budgets = shard_local_compaction(
                    gates_w.any(axis=0), n_shards
                )
                if budget < local_len:
                    budget_w = budget
                    # Tile size: a power-of-two divisor of the shared budget
                    # (in this branch every per-shard bucket is a pure power
                    # of two < local_len), floored at budget/8 so the tile
                    # loop never degenerates into one-image steps.  Shards
                    # run ceil(own_budget/tile) tiles; slack rows past a
                    # shard's own budget are 0-padded, gate-False entries.
                    tile = max(int(budgets.min()), budget // 8)
                    per_shard = gates_w.reshape(nq, n_shards, local_len)
                    gates_exec = (
                        np.take_along_axis(per_shard, local_idx[None], axis=2)
                        & pad_mask[None]
                    ).reshape(nq, n_shards * budget)
                else:
                    local_idx = budgets = None
            if local_idx is None:
                gates_exec = gates_w
            c, d = window_job(mds, gates_exec, local_idx, budgets, tile,
                              local_len)
            coadds = c if coadds is None else coadds + c
            depths = d if depths is None else depths + d
            packs_scanned += (
                int(((budgets + tile - 1) // tile * tile).sum())
                if budgets is not None else n_shards * local_len
            )
            scan_budget_max = max(scan_budget_max, budget_w)
            # Locality stats derive from the *flat* gate the mesh actually
            # executes: pack identity is lost in the flattened layout, so
            # the honest "containers opened" count is resident (window,
            # shard) slabs touched (see JobStats.packs_touched).
            shards_touched += gates_w.reshape(nq, n_shards, local_len).any(
                axis=2
            ).sum(axis=1)
            if i + 1 < len(flat_windows):
                mds = mesh_window(*flat_windows[i + 1])  # prefetch next slab
        _sync(coadds)
        t2 = time.perf_counter()

        results = []
        for qi, q in enumerate(queries):
            stats = JobStats(
                method="distributed_sql_structured",
                files_considered=len(all_ids),
                files_contributing=len(id_sets[qi]),
                packs_touched=int(shards_touched[qi]),
                t_locate_s=t_locate,
                t_map_reduce_s=t2 - t1,
                t_total_s=t_locate + (t2 - t1),
                # One windowed shard_map job serves the whole multi-query
                # batch; attribute it to the first result so summing stats
                # is honest.
                dispatches=len(flat_windows) if qi == 0 else 0,
                packs_gated=int(shards_touched[qi]),
                packs_scanned=packs_scanned if qi == 0 else 0,
                scan_budget=scan_budget_max,
                windows=len(flat_windows),
                chunk_uploads=(self.residency.uploads - up0) if qi == 0 else 0,
                residency_hits=(self.residency.hits - hit0) if qi == 0 else 0,
                residency_evictions=(self.residency.evictions - ev0)
                if qi == 0 else 0,
                peak_resident_bytes=self._peak_resident_bytes(),
            )
            results.append(
                CoaddResult(np.asarray(coadds[qi]), np.asarray(depths[qi]), stats)
            )
        return results
