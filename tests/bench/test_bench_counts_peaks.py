"""The work counts follow the reference's samples; the peaks table knows
only the chips it names."""

import json

import numpy as np
import pytest
from bench_tiny import REPO, TINY_FRAME

from bench import archive, counts, peaks, reference


def _tiny_cfg():
    cfg = json.loads((REPO / "bench/configs/sdss_s82_resident.json").read_text())
    cfg.update(TINY_FRAME, runs=3, fields=2, camcols=2, bands=2)
    return cfg


def _brute_samples(arc, band, box, npix):
    """Output-pixel samples that fall inside a frame, one frame at a time."""
    gr, gd = reference.grid_sky(box, npix)
    h, w = arc.pixels.shape[1:]
    n = 0
    for k in range(len(arc)):
        if arc.band[k] != band:
            continue
        sx, sy = reference.sky_to_pixel(gr, gd, arc.wcs[k])
        n += int(((sx >= 0) & (sx <= w - 1) & (sy >= 0) & (sy <= h - 1)).sum())
    return n


@pytest.mark.parametrize("npix", [8, 24])
def test_count_follows_reference_samples(npix):
    cfg = _tiny_cfg()
    arc = archive.make_archive(cfg, seed=5)
    geo = archive.Geometry.of(cfg)
    half = 0.5 * npix * geo.scale_deg
    ra = geo.ra_edges[1]
    dec = geo.dec_edges[1]
    box = (ra - half, ra + half, dec - half, dec + half)
    _, depth, _ = reference.coadd(arc, 1, box, npix)
    samples = float(depth.sum())
    assert samples == _brute_samples(arc, 1, box, npix) > 0
    ops, nbytes = counts.query_work(samples, npix)
    assert ops == counts.OPS * samples
    assert nbytes == 4 * samples + 8 * npix * npix
    assert counts.OPS == sum(counts.OPS_PER_SAMPLE.values()) == 50


def test_roofline_bound_is_named():
    p = peaks.peaks_for("TPU v5 lite")
    t, bound = counts.roofline_seconds(1e6, 1e9, p)
    assert bound == "bytes" and t == pytest.approx(1e9 / 819e9)
    t, bound = counts.roofline_seconds(1e15, 1.0, p)
    assert bound == "compute" and t == pytest.approx(1e15 / 197e12)


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks_for("cpu")
    assert peaks.peaks_for("TPU v5e")["hbm_bytes_per_s"] == 819e9


def test_archive_is_seeded():
    cfg = _tiny_cfg()
    a, b = archive.make_archive(cfg, 2**31 + 3), archive.make_archive(cfg, 2**31 + 3)
    c = archive.make_archive(cfg, 2**31 + 4)
    assert np.array_equal(a.pixels, b.pixels) and np.array_equal(a.wcs, b.wcs)
    assert not np.array_equal(a.pixels, c.pixels)
    assert np.array_equal(a.wcs, a.wcs.astype(np.float32).astype(np.float64))


def test_frames_rendered_alone_match_the_whole_archive():
    cfg = _tiny_cfg()
    whole = archive.make_archive(cfg, 2**31 + 3)
    some = archive.make_archive(cfg, 2**31 + 3, frames=[0, 5, len(whole) - 1])
    assert sorted(some.pixels) == [0, 5, len(whole) - 1]
    for k, px in some.pixels.items():
        assert np.array_equal(px, whole.pixels[k])
    assert np.array_equal(some.bounds, whole.bounds)


@pytest.mark.parametrize("seed", [7, 2**31 + 9])
def test_stack_blocks_are_the_same_work_on_every_seed(seed):
    """At full SDSS geometry (no pixels rendered), each block of four boxes
    overlaps 16, 32, 32 and 64 frames of its band in some order."""
    import types

    from bench import harness

    cfg = json.loads((REPO / "bench/configs/sdss_s82_resident.json").read_text())
    meta, wcs, _, bounds, _ = archive.frame_table(cfg, seed)
    arc = archive.Archive(cfg, None, wcs, meta["run"], meta["camcol"],
                          meta["field"], meta["band"], None, bounds)
    tr = json.loads((REPO / "bench/traffic/stack_closed.json").read_text())
    driver = harness.load_module(REPO / "bench/drivers/closed_stack.py")
    gen = driver.queries(types.SimpleNamespace(cfg=cfg, traffic=tr, seed=seed), 10)
    for _ in range(25):
        block = sorted(len(arc.overlapping(b, box)) for b, box, _ in
                       (next(gen) for _ in range(4)))
        assert block == [16, 32, 32, 64]


def test_every_seed_stacks_the_same_work():
    """The pointings do not follow the seed, each kind of box sits at the
    same place in its cell, and each kind takes the fields in turn: two
    seeds' windows differ only in which camcols and bands they visit, and
    in what order."""
    import types

    from bench import harness

    cfg = json.loads((REPO / "bench/configs/sdss_s82_resident.json").read_text())
    geo = archive.Geometry.of(cfg)
    tr = json.loads((REPO / "bench/traffic/stack_closed.json").read_text())
    driver = harness.load_module(REPO / "bench/drivers/closed_stack.py")

    def placements(seed):
        gen = driver.queries(types.SimpleNamespace(cfg=cfg, traffic=tr, seed=seed), 10)
        out = []
        for _, box, _ in (next(gen) for _ in range(16)):
            out.append((int((box[0] - geo.ra_start) // geo.field_deg),
                        round(((box[0] - geo.ra_start) % geo.field_deg) / geo.scale_deg, 6),
                        round(((box[2] - geo.dec_min) % geo.camcol_deg) / geo.scale_deg, 6)))
        return sorted(out)

    seeds = (7, 2**31 + 9, 4_100_000_007)
    wcs = [archive.frame_table(cfg, s)[1] for s in seeds]
    assert all(np.array_equal(w, wcs[0]) for w in wcs[1:])
    first = placements(seeds[0])
    assert len(set(first)) == 6
    assert all(placements(s) == first for s in seeds[1:])
    gen = driver.queries(types.SimpleNamespace(cfg=cfg, traffic=tr, seed=seeds[0]), 10)
    boxes = [(b, box) for b, box, _ in (next(gen) for _ in range(100))]
    assert len(set(boxes)) == len(boxes)

