"""Closed loop: one caller stacks boxes back to back.

Each query goes through ``engine.plan`` and then ``engine.execute``, the
resident path; the service is bypassed.  Queries come in blocks of four,
one box of each way a box can lie on the frame grid (inside one frame,
across a field edge, across a camcol edge, across both), in seeded order.
Each kind of box sits at one place within its cell of the frame grid,
drawn once from the traffic's ``placement_seed``.  Along RA a kind's
boxes take the slice's fields (or field edges) in turn, because the frames
a pack holds beside the box's, which the scan reads masked, differ from
one field to the other and change the scan's time by up to 7% on a TPU
v5e.  The seed picks the camcol (or camcol edge) and the band, without
repeating one before all of that kind are used, which moves no time.  The
archive's pointings do not depend on the seed either, so every seed
stacks the same work, in another order.  The window runs for the given
seconds and then to the end of its block.

Traffic parameters: ``npix`` (output grid, native scale), ``reduce``,
``method``, ``compare`` (answers the check draws from the window),
``placement_seed``.
"""

from __future__ import annotations

import itertools
import time

import numpy as np

from bench.archive import BANDS, Geometry, seed_words

CLASSES = ((False, False), (True, False), (False, True), (True, True))


def _cells(rng, n_ra, n_dec, n_bands):
    """Endless (ra range, dec range, band) picks: the RA ranges in turn,
    and a seeded pass over every (dec range, band) before any repeats."""
    combos = list(itertools.product(range(n_dec), range(n_bands)))
    turn = itertools.count()
    while True:
        for k in rng.permutation(len(combos)):
            yield (next(turn) % n_ra, *combos[k])


def queries(ctx, stream: int):
    """Endless (band, box, npix) blocks of four from the seed."""
    geo = Geometry.of(ctx.cfg)
    npix = ctx.traffic["npix"]
    box_deg = npix * geo.scale_deg
    half = 0.5 * box_deg
    place = np.random.default_rng(seed_words(ctx.traffic["placement_seed"]))
    rng = np.random.default_rng(seed_words(ctx.seed, stream))
    kinds = []
    for ra_cross, dec_cross in CLASSES:
        ra_r = geo.centre_ranges(box_deg, "ra", ra_cross)
        dec_r = geo.centre_ranges(box_deg, "dec", dec_cross)
        u = place.uniform(size=2)
        kinds.append((ra_r, dec_r, u,
                      _cells(rng, len(ra_r), len(dec_r), ctx.cfg["bands"])))
    while True:
        for k in rng.permutation(len(CLASSES)):
            ra_r, dec_r, u, cells = kinds[k]
            i, j, band = next(cells)
            ra = ra_r[i][0] + u[0] * (ra_r[i][1] - ra_r[i][0])
            dec = dec_r[j][0] + u[1] * (dec_r[j][1] - dec_r[j][0])
            yield int(band), (ra - half, ra + half, dec - half, dec + half), npix


def _query(band, box, npix):
    from repro.core import CoaddQuery

    return CoaddQuery(band=BANDS[band], ra_bounds=(box[0], box[1]),
                      dec_bounds=(box[2], box[3]), npix=npix)


def _run_one(ctx, band, box, npix):
    eng, tr = ctx.engine, ctx.traffic
    with ctx.span("bench.plan"):
        plan = eng.plan(_query(band, box, npix), tr["method"], tr["reduce"])
    with ctx.span("bench.execute"):
        return eng.execute(plan)


def warm(ctx):
    """One block of four from a stream of its own: every program shape
    the window's blocks use."""
    gen = queries(ctx, stream=20)
    for _ in CLASSES:
        _run_one(ctx, *next(gen))


def window(ctx, seconds):
    gen = queries(ctx, stream=10)
    done = []
    t0 = time.perf_counter()
    deadline = t0 + seconds
    while True:
        band, box, npix = next(gen)
        res = _run_one(ctx, band, box, npix)
        t = time.perf_counter()
        s = res.stats
        done.append({
            "band": band, "box": box, "npix": npix,
            "frames": len(ctx.archive.overlapping(band, box)),
            "samples": float(np.sum(res.depth)),
            "t_locate_s": s.t_locate_s, "packs_scanned": s.packs_scanned,
            "dispatched": True, "result": res})
        if t >= deadline and len(done) % len(CLASSES) == 0:
            break
    return {"window_s": t - t0, "attempted": len(done), "failed": 0,
            "queries": done}


def end_to_end(record):
    return {"frames_per_s": sum(q["frames"] for q in record["queries"])
            / record["window_s"]}


def sample(ctx, record):
    """``compare`` answers drawn from the seed among those of the window."""
    qs = record["queries"]
    rng = np.random.default_rng(seed_words(ctx.seed, 11))
    pick = rng.choice(len(qs), size=min(ctx.traffic["compare"], len(qs)),
                      replace=False)
    out = [(qs[i]["band"], qs[i]["box"], qs[i]["npix"],
            qs[i]["result"].coadd, qs[i]["result"].depth) for i in sorted(pick)]
    for q in qs:
        q.pop("result")
    return out


def control_sample(ctx):
    """The queries the control answers: as many as a run compares, drawn
    from the seed among the window's first two blocks."""
    gen = queries(ctx, stream=10)
    first = [next(gen) for _ in range(2 * len(CLASSES))]
    rng = np.random.default_rng(seed_words(ctx.seed, 11))
    pick = rng.choice(len(first), size=ctx.traffic["compare"], replace=False)
    return [first[i] for i in sorted(pick)]
