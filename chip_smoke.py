#!/usr/bin/env python3
"""Bring-up check: the coadd engine and service on a TPU at SDSS frame size.

    python3 chip_smoke.py                  # one TPU chip: phases a-e
    python3 chip_smoke.py --four-chips     # run_distributed on a (4, 1) mesh
    python3 chip_smoke.py --rehearse-cpu   # every phase, tiny sizes, CPU

The archive is a seeded synthetic Stripe 82 slice with SDSS's frame shape:
2048 (Dec) x 1489 (RA) pixels at 0.396"/px, 8 runs x 6 camcols x 5 bands x
2 fields = 480 frames (5.9 GB of float32 pixels), resident in HBM under the
structured layout.  Every answer is checked against a float64 numpy
reference built from the survey frames with `geometry.sky_to_pixel` and
bilinear sampling, independently of the engine:

  a. resident query     one sql_structured r-band query at npix 1024
  b. PSF-matched query  the same query homogenized with the measured stamps
  c. service            a coalesced burst and a repeat through CoaddService
  d. streaming          the same query under a third-of-the-archive budget
  e. kernel lane        the Pallas kernels (Mosaic, not the interpreter)
                        against the XLA lane on 32x32-frame surveys

``--four-chips`` runs only the sharded path: the same archive over a
(4, 1) ("data", "model") mesh, compared with a one-chip `engine.run` and
the reference.  Each phase prints one line of wall seconds, compile
seconds and errors; the last line is one JSON object naming the device.
Off the TPU (without ``--rehearse-cpu``) the script exits non-zero and
prints no result.  It is a one-shot check that the system runs on the
chip, not a benchmark.
"""

from __future__ import annotations

import argparse
import asyncio
import dataclasses
import json
import sys
import time
import traceback
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402

from repro.compile_cache import enable_compile_cache  # noqa: E402
from repro.core import (  # noqa: E402
    CoaddEngine,
    CoaddQuery,
    CoaddService,
    SurveyConfig,
    geometry,
    make_survey,
    psf,
)
from repro.core.mapper import query_grid_sky  # noqa: E402

# Pass criteria against the float64 reference: depth (a coverage count)
# equal on nearly every pixel — a frame edge sample can flip between
# float32 and float64 — and the normalized coadd within REL_TOL wherever
# both depths agree and are positive.
DEPTH_AGREE_MIN = 0.999
REL_TOL = 1e-3
PIXEL_ARCSEC = 0.396


@dataclasses.dataclass(frozen=True)
class Sizes:
    """One run's shapes: the SDSS archive and the kernel-lane surveys."""

    frame_hw: tuple          # (height = Dec, width = RA) pixels
    psf_sigma_px: float      # nominal seeing, per run jittered x0.85-1.35
    npix: int                # phase a-d output grid
    kernel_survey: SurveyConfig
    kernel_npix: tuple


CHIP = Sizes(
    frame_hw=(2048, 1489),
    psf_sigma_px=1.5,                        # 1.4" FWHM at 0.396"/px
    npix=1024,
    kernel_survey=SurveyConfig(),            # 2880 frames of 32x32
    kernel_npix=(64, 128),
)
# Same sky and the same 480-frame layout, 43x fewer pixels per frame.
REHEARSE = Sizes(
    frame_hw=(48, 35),
    psf_sigma_px=1.0,
    npix=32,
    kernel_survey=SurveyConfig(n_runs=2, n_fields=3, n_sources=120),
    kernel_npix=(64, 128),
)


def sdss_config(sizes: Sizes, seed: int, **kw) -> SurveyConfig:
    """8 runs x 6 camcols x 5 bands x 2 fields of SDSS-shaped frames."""
    h, w = sizes.frame_hw
    base = dict(
        n_runs=8, n_camcols=6, n_bands=5, n_fields=2,
        height=h, width=w,
        camcol_dec_deg=2048 * PIXEL_ARCSEC / 3600,   # 0.2253 deg
        field_ra_deg=1489 * PIXEL_ARCSEC / 3600,     # 0.1638 deg
        psf_sigma_px=sizes.psf_sigma_px,
        n_sources=2000,
        seed=seed,
    )
    base.update(kw)
    return SurveyConfig(**base)


def corner_query(cfg: SurveyConfig, npix: int, d_ra=0.0, d_dec=0.0):
    """r-band box at native SDSS scale around the corner of 4 frames."""
    half = 1024 * PIXEL_ARCSEC / 3600 / 2
    ra = cfg.ra_start + cfg.field_ra_deg + d_ra
    dec = cfg.dec_center + d_dec
    return CoaddQuery(band="r", ra_bounds=(ra - half, ra + half),
                      dec_bounds=(dec - half, dec + half), npix=npix)


class SmokeFailure(Exception):
    """A phase's result broke its pass criterion."""


def require(ok, msg):
    if not ok:
        raise SmokeFailure(msg)


# ----- the float64 numpy reference -----------------------------------------

def _bilinear(img, sx, sy):
    h, w = img.shape
    x0 = np.floor(sx)
    y0 = np.floor(sy)
    dx, dy = sx - x0, sy - y0
    inside = (sx >= 0) & (sx <= w - 1) & (sy >= 0) & (sy <= h - 1)
    xa = np.clip(x0.astype(np.int64), 0, w - 1)
    xb = np.clip(x0.astype(np.int64) + 1, 0, w - 1)
    ya = np.clip(y0.astype(np.int64), 0, h - 1)
    yb = np.clip(y0.astype(np.int64) + 1, 0, h - 1)
    v = (img[ya, xa] * (1 - dx) * (1 - dy) + img[ya, xb] * dx * (1 - dy)
         + img[yb, xa] * (1 - dx) * dy + img[yb, xb] * dx * dy)
    return np.where(inside, v, 0.0), inside.astype(np.float64)


def _correlate_edge(img, k):
    """Edge-clamped cross-correlation with a (K, K) kernel, via the FFT."""
    kh, kw = k.shape
    p = np.pad(img, ((kh // 2, kh // 2), (kw // 2, kw // 2)), mode="edge")
    shape = p.shape
    full = np.fft.irfft2(
        np.fft.rfft2(p) * np.fft.rfft2(k[::-1, ::-1], s=shape), s=shape
    )
    return full[kh - 1:kh - 1 + img.shape[0], kw - 1:kw - 1 + img.shape[1]]


def reference(survey, query, psf_target=None):
    """Float64 coadd + depth of the frames of ``query``'s band that overlap
    its box; with ``psf_target`` each frame is first correlated with its
    measured-stamp homogenization kernel."""
    gr, gd = (a.astype(np.float64) for a in query_grid_sky(query))
    coadd = np.zeros(gr.shape)
    depth = np.zeros(gr.shape)
    frames = [im for im in survey.images
              if im.band_id == query.band_id
              and geometry.boxes_intersect(im.bounds, query.bounds)]
    kernels = None
    if psf_target is not None:
        kernels = psf.homogenization_bank(
            np.stack([im.psf_stamp for im in frames]),
            np.array([im.psf_sigma for im in frames]), psf_target,
        )
    for i, im in enumerate(frames):
        px = im.pixels.astype(np.float64)
        if kernels is not None:
            px = _correlate_edge(px, kernels[i].astype(np.float64))
        sx, sy = geometry.sky_to_pixel(
            gr, gd, im.wcs.to_vector().astype(np.float64))
        v, m = _bilinear(px, sx, sy)
        coadd += v
        depth += m
    return coadd, depth, len(frames)


def compare(coadd, depth, ref_c, ref_d, what="reference"):
    """Errors of one result against another; raises past the criteria."""
    require(ref_d.max() > 0, f"{what} covers nothing")
    require(np.isfinite(coadd).all() and np.isfinite(ref_c).all(),
            f"non-finite coadd values, compared with {what}")
    agree = depth == ref_d
    frac = float(agree.mean())
    both = agree & (ref_d > 0)
    norm = coadd[both] / depth[both]
    ref = ref_c[both] / ref_d[both]
    rel = np.abs(norm - ref) / np.maximum(np.abs(ref), 1e-30)
    max_rel = float(rel.max())
    require(frac >= DEPTH_AGREE_MIN,
            f"depth agrees with {what} on {frac:.6f} of pixels "
            f"< {DEPTH_AGREE_MIN}")
    require(max_rel <= REL_TOL,
            f"normalized coadd off {what} by {max_rel:.3e} relative > "
            f"{REL_TOL}")
    return {"depth_agree": frac, "max_rel_err": max_rel,
            "bitwise": bool(np.array_equal(coadd, ref_c)
                            and np.array_equal(depth, ref_d))}


# ----- phase bookkeeping ----------------------------------------------------

class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling (or loading from
    the persistent cache), and persistent-cache hits, as JAX reports them."""

    def __init__(self):
        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event.startswith("/jax/core/compile/"):
            self.seconds += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


class Phases:
    """Runs each phase, prints its line, and remembers failures."""

    def __init__(self, clock: CompileClock):
        self.clock = clock
        self.failed = []

    def run(self, name, fn, *args):
        t0, c0, h0 = time.perf_counter(), self.clock.seconds, self.clock.cache_hits
        try:
            info = fn(*args) or {}
        except Exception as exc:  # report every phase, then fail the run
            traceback.print_exc()
            self.failed.append(name)
            print(f"phase {name}: FAIL {type(exc).__name__}: {exc}", flush=True)
            return None
        fields = {
            "wall_s": time.perf_counter() - t0,
            "compile_s": self.clock.seconds - c0,
            "compile_cache_hits": self.clock.cache_hits - h0,
            **info,
        }
        print(f"phase {name}: ok " + " ".join(f"{k}={v}" for k, v in
                                              fields.items()), flush=True)
        return info


def device_bytes(key="bytes_in_use"):
    """Per-device memory statistic, or None where the backend has none."""
    stats = [d.memory_stats() for d in jax.devices()]
    if any(s is None or key not in s for s in stats):
        return None
    return [int(s[key]) for s in stats]


# ----- phases ---------------------------------------------------------------

def phase_resident(ctx):
    eng, q = ctx["engine"], ctx["qa"]
    t0 = time.perf_counter()
    res = eng.run(q, "sql_structured")
    cold = time.perf_counter() - t0
    t0 = time.perf_counter()
    again = eng.run(q, "sql_structured")
    warm = time.perf_counter() - t0
    require(np.array_equal(res.coadd, again.coadd), "repeat run differs")
    ctx["a"] = res
    ref_c, ref_d, n = ctx["ref_a"]
    return {"frames": res.stats.files_contributing, "ref_frames": n,
            "cold_s": cold, "warm_s": warm,
            **compare(res.coadd, res.depth, ref_c, ref_d)}


def phase_psf(ctx):
    eng, q, survey = ctx["engine"], ctx["qa"], ctx["survey"]
    target = 1.05 * float(max(psf.stamp_sigma(im.psf_stamp)
                              for im in survey.images))
    eng.match_psf_sigma = target
    try:
        res = eng.run(q, "sql_structured")
    finally:
        eng.match_psf_sigma = None
    ref_c, ref_d, n = reference(survey, q, psf_target=target)
    return {"target_sigma_px": target, "frames": n,
            **compare(res.coadd, res.depth, ref_c, ref_d)}


def phase_service(ctx):
    eng, survey, cfg = ctx["engine"], ctx["survey"], ctx["cfg"]
    qa = ctx["qa"]
    qb = corner_query(cfg, qa.npix, d_ra=0.03)
    qc = corner_query(cfg, qa.npix, d_dec=-0.03)

    async def serve():
        svc = CoaddService(eng, max_batch=8)
        burst = [asyncio.ensure_future(svc.submit(q)) for q in (qa, qb, qc)]
        while svc.queue_depth < len(burst):
            await asyncio.sleep(0.005)
        async with svc:          # queued before start: one coalesced drain
            first = await asyncio.gather(*burst)
            repeat = await svc.submit(qa)
        return svc.stats, first, repeat

    stats, (ra, rb, rc), repeat = asyncio.run(serve())
    require(stats.dispatches == 1 and stats.coalesce_factor == 3.0,
            f"burst took {stats.dispatches} dispatches, coalesce "
            f"{stats.coalesce_factor}")
    require(stats.cache_hits == 1 and repeat is ra, "repeat missed the cache")
    out = {"dispatches": stats.dispatches,
           "coalesce": stats.coalesce_factor, "cache_hits": stats.cache_hits}
    ref_c, ref_d, _ = ctx["ref_a"]
    out["a_vs_ref"] = compare(ra.coadd, ra.depth, ref_c, ref_d)["max_rel_err"]
    if ctx.get("a") is not None:
        solo = compare(ra.coadd, ra.depth, ctx["a"].coadd, ctx["a"].depth,
                       "solo engine.run")
        out["a_vs_solo_bitwise"] = solo["bitwise"]
        out["a_vs_solo_rel"] = solo["max_rel_err"]
    for name, q, r in (("b", qb, rb), ("c", qc, rc)):
        rc_, rd_, _ = reference(survey, q)
        out[f"{name}_vs_ref"] = compare(r.coadd, r.depth, rc_, rd_)[
            "max_rel_err"]
    return out


def phase_streaming(ctx):
    survey, q = ctx["survey"], ctx["qa"]
    archive = ctx["archive_bytes"]
    eng = CoaddEngine(survey, pack_capacity=16,
                      device_budget_bytes=archive // 3)
    res = eng.run(q, "sql_structured")
    s = res.stats
    require(s.windows > 1, f"streamed in {s.windows} window")
    require(s.retries == 0 and not s.partial,
            f"retries={s.retries} partial={s.partial}")
    ref_c, ref_d, _ = ctx["ref_a"]
    out = {"budget_bytes": archive // 3, "windows": s.windows,
           "uploads": s.chunk_uploads, "retries": s.retries,
           "partial": s.partial,
           **compare(res.coadd, res.depth, ref_c, ref_d)}
    if ctx.get("a") is not None:
        eager = compare(res.coadd, res.depth, ctx["a"].coadd, ctx["a"].depth,
                        "the resident answer")
        out["vs_resident_bitwise"] = eager["bitwise"]
        out["vs_resident_rel"] = eager["max_rel_err"]
    return out


def phase_kernel_lane(ctx):
    sizes, seed = ctx["sizes"], ctx["seed"]
    on_tpu = jax.default_backend() == "tpu"
    survey = make_survey(dataclasses.replace(sizes.kernel_survey, seed=seed))
    kern = CoaddEngine(survey, pack_capacity=64, use_kernel=True)
    xla = CoaddEngine(survey, pack_capacity=64)
    box = dict(band="r", ra_bounds=(37.6, 38.6), dec_bounds=(-0.55, 0.45))
    worst = 1.05 * float(max(psf.stamp_sigma(im.psf_stamp)
                             for im in survey.images))
    cases = [(f"mean{n}", CoaddQuery(npix=n, **box), "mean", None)
             for n in sizes.kernel_npix]
    n0 = sizes.kernel_npix[0]
    cases += [(f"median{n0}", CoaddQuery(npix=n0, **box), "median", None),
              (f"psf2d{n0}", CoaddQuery(npix=n0, **box), "mean", worst)]
    out, programs = {}, 0
    for name, q, reduce, target in cases:
        kern.match_psf_sigma = xla.match_psf_sigma = target
        rk = kern.run(q, "sql_structured", reduce=reduce)
        rx = xla.run(q, "sql_structured", reduce=reduce)
        out[name] = compare(rk.coadd, rk.depth, rx.coadd, rx.depth,
                            f"the XLA lane ({name})")["max_rel_err"]
        hlo = kern.lower(kern.plan(q, "sql_structured", reduce)) \
            .compile().as_text()
        # On a TPU the kernels must lower through Mosaic; on the CPU they
        # run in the interpreter and leave no custom call.
        require(("tpu_custom_call" in hlo) == on_tpu,
                f"{name}: tpu_custom_call "
                f"{'missing' if on_tpu else 'present'}")
        programs += 1
    kern.match_psf_sigma = xla.match_psf_sigma = None
    # Brick serving: materialized bricks merged by the mosaic kernel.
    bk = CoaddEngine(survey, pack_capacity=64, use_kernel=True,
                     brick_deg=0.5, brick_npix=64)
    bx = CoaddEngine(survey, pack_capacity=64, brick_deg=0.5, brick_npix=64)
    wq = bk.brick_grid.window_query(1, 2, 1, 2, "r")
    rk = bk.run(wq, "sql_structured", use_bricks=True)
    rx = bx.run(wq, "sql_structured", use_bricks=True)
    out["bricks"] = compare(rk.coadd, rk.depth, rx.coadd, rx.depth,
                            "the XLA lane (bricks)")["max_rel_err"]
    # A full-size frame cannot fit one VMEM grid step: refused, no fallback.
    h, w = CHIP.frame_hw
    big = make_survey(SurveyConfig(n_runs=1, n_camcols=1, n_bands=1,
                                   n_fields=1, height=h, width=w,
                                   n_sources=10, seed=seed))
    bq = CoaddQuery(band="u", ra_bounds=(37.05, 37.15),
                    dec_bounds=(-0.05, 0.05), npix=n0)
    try:
        CoaddEngine(big, use_kernel=True).run(bq, "sql_structured")
    except ValueError as exc:
        require("does not fit" in str(exc), str(exc))
    else:
        raise SmokeFailure(f"a {h}x{w} frame ran through the kernel lane")
    out["programs_checked"] = programs
    out["tpu_custom_call"] = on_tpu
    return out


def four_chip_phase(ctx):
    survey, q = ctx["survey"], ctx["qa"]
    mesh = jax.make_mesh((4, 1), ("data", "model"))
    eng = CoaddEngine(survey, pack_capacity=16)
    res = eng.run_distributed([q], mesh)[0]
    held = device_bytes()
    out = {"mesh": "4x1"}
    if held is not None:
        share = [b / ctx["archive_bytes"] for b in held]
        out["device_bytes"] = held
        require(all(0.2 <= s <= 0.35 for s in share),
                f"archive shares per device {share} are not about 1/4")
    ref_c, ref_d, _ = ctx["ref_a"]
    out.update(compare(res.coadd, res.depth, ref_c, ref_d))
    one = CoaddEngine(survey, pack_capacity=16).run(q, "sql_structured")
    vs = compare(res.coadd, res.depth, one.coadd, one.depth, "one chip")
    out["vs_one_chip_bitwise"] = vs["bitwise"]
    out["vs_one_chip_rel"] = vs["max_rel_err"]
    return out


# ----- main -----------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=82)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded path on a (4, 1) mesh")
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="every phase at tiny sizes, without a TPU")
    args = ap.parse_args(argv)

    devices = jax.devices()
    if not args.rehearse_cpu:
        if devices[0].platform != "tpu":
            print(f"chip_smoke: JAX found no TPU (platform "
                  f"{devices[0].platform}); nothing was run", file=sys.stderr)
            return 2
        # The CPU rehearsal runs inside test processes, whose JAX
        # configuration it leaves alone.
        print(f"compile cache: {enable_compile_cache()}", flush=True)
    if args.four_chips and len(devices) != 4:
        print(f"chip_smoke: --four-chips needs exactly 4 devices, found "
              f"{len(devices)}", file=sys.stderr)
        return 2
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    print(f"device: {json.dumps(device)}", flush=True)
    if not args.four_chips and len(devices) != 1:
        print(f"note: {len(devices)} devices visible; phases use device 0")

    sizes = REHEARSE if args.rehearse_cpu else CHIP
    clock = CompileClock()
    phases = Phases(clock)
    t0 = time.perf_counter()
    cfg = sdss_config(sizes, args.seed)
    survey = make_survey(cfg)
    engine = CoaddEngine(survey, pack_capacity=16, matched_pixel_cache=False)
    ds = engine.dataset("structured")
    archive = ds.chunk_nbytes(0, ds.n_packs)
    qa = corner_query(cfg, sizes.npix)
    print(f"survey: {len(survey)} frames of {cfg.height}x{cfg.width} "
          f"(seed {args.seed}) in {time.perf_counter() - t0:.1f}s; "
          f"structured archive {ds.n_packs} packs x {ds.capacity} = "
          f"{archive} bytes", flush=True)
    print("cut: matched-pixel cache off (a PSF-matched copy would double "
          "the resident archive); PSF matching convolves in the scan",
          flush=True)
    t0 = time.perf_counter()
    ctx = {"engine": engine, "survey": survey, "cfg": cfg, "qa": qa,
           "sizes": sizes, "seed": args.seed, "archive_bytes": archive,
           "ref_a": reference(survey, qa)}
    print(f"reference: {ctx['ref_a'][2]} frames, float64 numpy, "
          f"{time.perf_counter() - t0:.1f}s; pass: depth equal on >= "
          f"{DEPTH_AGREE_MIN} of pixels, normalized coadd within {REL_TOL} "
          "relative where depth > 0", flush=True)

    if args.four_chips:
        phases.run("four_chips", four_chip_phase, ctx)
    else:
        phases.run("a_resident", phase_resident, ctx)
        print(f"device memory: archive {archive} bytes resident, "
              f"peak_bytes_in_use {device_bytes('peak_bytes_in_use')}",
              flush=True)
        phases.run("b_psf_matched", phase_psf, ctx)
        phases.run("c_service", phase_service, ctx)
        phases.run("d_streaming", phase_streaming, ctx)
        phases.run("e_kernel_lane", phase_kernel_lane, ctx)
        print(f"device memory: peak_bytes_in_use "
              f"{device_bytes('peak_bytes_in_use')}", flush=True)
    print(f"compile: {clock.seconds:.1f}s total, {clock.cache_hits} "
          "persistent-cache hits", flush=True)
    if phases.failed:
        print(f"chip_smoke: failed phases {phases.failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
