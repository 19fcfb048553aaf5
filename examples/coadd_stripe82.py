"""Full Table-1-style comparison + a multi-query distributed job.

PYTHONPATH=src python examples/coadd_stripe82.py
(The distributed demo shards over every local device; with a single device
it is skipped with a message.  On the CPU,
XLA_FLAGS=--xla_force_host_platform_device_count=4 gives it four.)

PYTHONPATH=src python examples/coadd_stripe82.py --detect
runs only the seeded difference-imaging drill (DESIGN.md §11): inject
transients into the newest epoch, difference it against the brick-served
robust template, detect at 5 sigma, and exit nonzero unless >= 95% of the
injections are recovered with zero false positives — on the injected AND
the static sky.
"""
import argparse
import sys

import jax
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.core import CoaddEngine, CoaddQuery, METHODS, SurveyConfig, make_survey


def detect_drill(seed: int = 7, nsigma: float = 5.0) -> int:
    """Seeded transient-recovery drill; returns a process exit code."""
    from repro.core import (detect_sources, difference_image,
                            inject_transients, match_detections)

    cfg = SurveyConfig(n_runs=3, n_fields=5, n_sources=100,
                       height=20, width=20)
    query = CoaddQuery(band="r", ra_bounds=(37.3, 37.9),
                       dec_bounds=(-0.5, 0.3), npix=48)

    def run_sky(injected):
        sv = make_survey(cfg)
        truths = (inject_transients(sv, query, n=8, flux=400.0, seed=seed)
                  if injected else np.zeros((0, 2)))
        eng = CoaddEngine(sv, pack_capacity=16, match_psf_sigma=2.0)
        diff, da, db = difference_image(eng, query, reduce="clipped")
        cat = detect_sources(diff, da, db, nsigma=nsigma)
        return truths, cat

    truths, cat = run_sky(injected=True)
    recovered, spurious = match_detections(cat, query, truths)
    _, static_cat = run_sky(injected=False)
    ok = (recovered >= int(np.ceil(0.95 * len(truths)))
          and spurious == 0 and len(static_cat) == 0)
    print(f"detect drill: seed={seed} nsigma={nsigma} "
          f"recovered={recovered}/{len(truths)} spurious={spurious} "
          f"static_sky_detections={len(static_cat)} "
          f"-> {'PASS' if ok else 'FAIL'}")
    return 0 if ok else 1


_ap = argparse.ArgumentParser(description=__doc__)
_ap.add_argument("--detect", action="store_true",
                 help="run only the seeded difference-imaging drill")
_ap.add_argument("--seed", type=int, default=7)
_args = _ap.parse_args()
enable_compile_cache()
if _args.detect:
    sys.exit(detect_drill(seed=_args.seed))

survey = make_survey(SurveyConfig(n_runs=5, n_fields=8, n_sources=150,
                                  height=24, width=24))
engine = CoaddEngine(survey, pack_capacity=64)
large = CoaddQuery(band="r", ra_bounds=(37.4, 38.4), dec_bounds=(-0.5, 0.5), npix=128)
small = CoaddQuery(band="r", ra_bounds=(37.8, 38.05), dec_bounds=(-0.1, 0.15), npix=128)

print(f"{'method':32s} {'1deg considered':>16s} {'qdeg considered':>16s}")
for m in METHODS:
    r1 = engine.run(large, m)
    r2 = engine.run(small, m)
    print(f"{m:32s} {r1.stats.files_considered:16d} {r2.stats.files_considered:16d}")

# Batched multi-query single-host job (paper Fig. 5): one jitted dispatch.
batch = engine.run_batch([large, small], "sql_structured")
print(f"run_batch: {len(batch)} queries, "
      f"{sum(r.stats.dispatches for r in batch)} dispatch(es)")

# Robust stacking (DESIGN.md §11): the same query with outlier-rejecting
# estimators — the sigma-clipped mean re-scans once with fixed clip
# operands, the two-round median adds a binapprox histogram pass.
for red in ("clipped", "median"):
    rr = engine.run(large, "sql_structured", reduce=red)
    print(f"robust stack/{red}: passes={rr.stats.reduce_passes} "
          f"depth_max={rr.depth.max():.0f} "
          f"rejected={float((batch[0].depth - rr.depth).sum()):.1f} "
          f"coverage-units")

# PSF-homogenized coadd (DESIGN.md §7): convolve every exposure to a common
# target PSF before stacking, so the coadd has a well-defined point-spread
# function.  The target must sit at/above the *measured* widths (Moffat
# wings make those larger than the Gaussian-equivalent seeing) or the bank
# clamps — pick it from the stamps, like a production pipeline would.
from repro.core import psf  # noqa: E402

worst = 1.05 * float(
    max(psf.stamp_sigma(im.psf_stamp) for im in survey.images)
)
matched = CoaddEngine(survey, pack_capacity=64, match_psf_sigma=worst)
rm = matched.run(large, "sql_structured")
print(f"psf-homogenized to sigma={worst:.2f}px: depth_max={rm.depth.max():.0f} "
      f"(matched-pixel cache: {rm.stats.matched_cache_builds} build)")

# Fault-tolerant streaming (DESIGN.md §8): run the same query through a
# budgeted engine while a chaos schedule kills one chunk upload and poisons
# one pack's pixels with NaNs.  The WindowTracker retries the upload, scrubs
# the poison, and still produces the fault-free coadd — the per-query fault
# telemetry below is the audit trail.
from repro.core import ChaosInjector, FaultSchedule, PoisonSpec  # noqa: E402

ds = engine.exec_dataset("structured")[0]
budget = ds.chunk_nbytes(0, ds.n_packs) // 4  # 4x oversubscribed
# Aim the poison at a pack the query's gate actually opens, so the drill
# exercises the scrub-and-retry path rather than missing the query entirely.
gated = np.nonzero(engine._exec_gate(engine.plan(large, "sql_structured"))
                   .any(axis=1))[0]
drill = FaultSchedule(
    upload_fail_ordinals=(0,),
    poison=(PoisonSpec(pack=int(gated[0]), mode="nan", count=1),))
chaotic = CoaddEngine(survey, pack_capacity=64, device_budget_bytes=budget,
                      fault_injector=ChaosInjector(drill),
                      fault_backoff_s=1e-3)
clean = CoaddEngine(survey, pack_capacity=64, device_budget_bytes=budget)
rf = chaotic.run(large, "sql_structured")
rc = clean.run(large, "sql_structured")
s = rf.stats
print(f"chaos drill: bitwise_equal={bool(np.array_equal(rf.coadd, rc.coadd))} "
      f"retries={s.retries} speculative={s.speculative_windows} "
      f"quarantined={s.quarantined_packs} resumed={s.resumed_windows} "
      f"partial={s.partial}")

# Brick-tessellated materialized coadds (DESIGN.md §9): precompute the hot
# sky once, then serve repeat queries by mosaicking cached bricks.  The
# drill runs the same lattice window cold (misses materialize inline),
# warm (every tile a device-tier hit, zero archive scan), and spilled
# (device replicas dropped; tiles re-upload from the host copy) — all
# three bitwise-identical to the brick-free fresh scan.
bricky = CoaddEngine(survey, pack_capacity=64, brick_deg=0.5, brick_npix=64)
wq = bricky.brick_grid.window_query(1, 3, 1, 3, "r")
fresh = bricky.run_window(wq, "sql_structured")


def _brick_leg(name, r):
    s = r.stats
    print(f"brick drill/{name}: hit={s.bricks_hit} missed={s.bricks_missed} "
          f"spilled={s.bricks_spilled} "
          f"residual_packs_scanned={s.residual_packs_scanned} "
          f"bitwise_equal={bool(np.array_equal(r.coadd, fresh.coadd))}")


_brick_leg("cold", bricky.run(wq, "sql_structured", use_bricks=True))
_brick_leg("warm", bricky.run(wq, "sql_structured", use_bricks=True))
bricky.brick_store.drop_device()
_brick_leg("spilled", bricky.run(wq, "sql_structured", use_bricks=True))

# Batch-materialize the whole r-band lattice; the four drilled bricks are
# already in the store, so the journal skips them.
report = bricky.materialize_bricks(bands=("r",))
print(f"materialize_bricks: {len(report.tasks)} bricks, "
      f"completed={report.completed} skipped={report.skipped} "
      f"partial={report.partial_bricks}")

# Durable crash recovery (DESIGN.md §8.1): journal window partials to disk
# so a resume survives *process death*, not just an in-process kill.  The
# drill kills a journaled streaming query after its first window, then
# hands the same journal_dir to a brand-new engine — as a fresh process
# would — which replays the finished window from disk, re-dispatches only
# the missing ones, and reproduces the fault-free coadd bitwise.
import tempfile  # noqa: E402

from repro.core import FatalFault  # noqa: E402

jdir = tempfile.mkdtemp(prefix="coadd-journal-")
doomed = CoaddEngine(survey, pack_capacity=64, device_budget_bytes=budget,
                     journal_dir=jdir,
                     fault_injector=ChaosInjector(
                         FaultSchedule(kill_after_windows=1)))
try:
    doomed.run(large, "sql_structured")
except FatalFault as e:
    print(f"durable drill: query killed mid-stream ({e})")
revived = CoaddEngine(survey, pack_capacity=64, device_budget_bytes=budget,
                      journal_dir=jdir)
rr = revived.run(large, "sql_structured")
print(f"durable drill: resumed_windows={rr.stats.resumed_windows} "
      f"bitwise_equal={bool(np.array_equal(rr.coadd, rc.coadd))} "
      f"journals_left={revived.journal_store.jobs()}")

# Multi-query distributed job (paper Fig. 5: parallel reducers over queries).
n = len(jax.devices())
if n == 1:
    print("distributed: skipped (one device; a mesh needs several)")
    sys.exit(0)
mesh = jax.make_mesh((n, 1), ("data", "model"))
queries = [
    CoaddQuery(band="g", ra_bounds=(37.4, 38.0), dec_bounds=(-0.4, 0.2), npix=64),
    CoaddQuery(band="r", ra_bounds=(37.6, 38.2), dec_bounds=(-0.2, 0.4), npix=64),
]
results = engine.run_distributed(queries, mesh, data_axes=("data",), model_axis=None)
for q, r in zip(queries, results):
    print(f"distributed band={q.band}: contributing={r.stats.files_contributing} "
          f"depth_max={r.depth.max():.0f}")
