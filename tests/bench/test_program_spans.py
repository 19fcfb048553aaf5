"""The reading of the program's own spans: medians of a span's duration,
bytes moved per query, and device idle time named for the innermost
program span, on a small trace written out by hand in the form
`program_spans.read` gives, on a CPU trace of the benchmark's tiny
engine, and on the trace of a traced window of ``s82_stack`` on one TPU v5
lite (``data/s82_stack_v5e_spans.xplane.pb.gz``), against what that run
printed (``data/s82_stack_v5e_spans.json``)."""

import gzip
import json
import shutil
from pathlib import Path

import pytest

from bench import program_spans as ps
from bench import trace_reduce as tr

# Times in ns.  Two queries: each scan (device) runs between its query's
# dispatch and the end of its sync; the host prepares the next query and
# fetches the last answer while the device idles.
SUMMARY = {
    "devices": {"/device:TPU:0": {
        "modules": [["jit__coadd_scan_sparse(7)", 100, 400],
                    ["jit__coadd_scan_sparse(7)", 900, 300]],
        "ops": [["while.1", 100, 400], ["while.1", 900, 300]],
    }},
    "spans": [["bench.window", 0, 1400], ["bench.execute", 0, 640],
              ["bench.plan", 650, 50], ["bench.execute", 700, 600]],
}


def _q(t0, prep, disp, sync, fetch, h2d=(1000, 30), d2h=8):
    """One query's execute spans: prepare (grid, compact, dispatch), sync,
    fetch, with start offsets and lengths as given."""
    g = prep[0] + 2
    c = g + 2 * (disp[0] - g) // 3
    return [
        ["coadd.execute", t0, fetch[0] + fetch[1] - t0, {}],
        ["coadd.execute.prepare", prep[0], prep[1], {}],
        ["coadd.execute.grid", g, c - g, {"h2d_bytes": h2d[0]}],
        ["coadd.execute.compact", c, disp[0] - c, {"h2d_bytes": h2d[1]}],
        ["coadd.execute.dispatch", disp[0], disp[1], {}],
        ["coadd.execute.sync", sync[0], sync[1], {}],
        ["coadd.execute.fetch", fetch[0], fetch[1], {"d2h_bytes": d2h}],
    ]


# Query 1: prepare [0, 96) (grid [2, 54), compact [54, 80), dispatch
# [80, 96)); sync [96, 500); fetch [500, 620).  Query 2, after plan
# [650, 700): prepare [700, 890) (grid [702, 820), compact [820, 880),
# dispatch [880, 890)); sync [890, 1200); fetch [1200, 1290).
PROGRAM = sorted(
    _q(0, (0, 96), (80, 16), (96, 404), (500, 120))
    + [["coadd.plan", 650, 50, {}], ["coadd.plan.locate", 660, 30, {}]]
    + _q(700, (700, 190), (880, 10), (890, 310), (1200, 90), d2h=12),
    key=lambda s: s[1])


def test_medians_and_transfer_of_the_window_queries():
    assert ps.median_ms(PROGRAM, "coadd.execute.prepare", 0, 1400) == \
        pytest.approx((96 + 190) / 2 / 1e6)
    assert ps.median_ms(PROGRAM, "coadd.execute.fetch", 0, 1400) == \
        pytest.approx((120 + 90) / 2 / 1e6)
    assert ps.transfer_mb(PROGRAM, 0, 1400) == pytest.approx((1038 + 1042) / 2 / 1e6)
    # Only the spans that start inside the window count.
    assert ps.median_ms(PROGRAM, "coadd.execute.prepare", 600, 1400) == \
        pytest.approx(190 / 1e6)
    assert ps.transfer_mb(PROGRAM, 600, 1400) == pytest.approx(1042 / 1e6)
    assert len(ps.queries(PROGRAM, 0, 1400)) == 2


def test_no_program_span_no_reading():
    for name in ("coadd.execute.prepare", "coadd.execute.fetch"):
        assert ps.median_ms([], name, 0, 1400) is None
    assert ps.transfer_mb([], 0, 1400) is None
    assert ps.median_ms(PROGRAM, "coadd.execute.prepare", 1300, 1400) is None


def test_innermost_span_takes_the_time():
    # [500, 900): fetch to 620, nothing to 650, plan, its locate
    # [660, 690), plan again to 700; then prepare (which starts with its
    # execute and is the shorter) to 702, grid, compact, dispatch, sync.
    got = ps.innermost(PROGRAM, 500, 900)
    assert got == {"coadd.execute.fetch": 120, "none": 30, "coadd.plan": 20,
                   "coadd.plan.locate": 30, "coadd.execute.prepare": 2,
                   "coadd.execute.grid": 118, "coadd.execute.compact": 60,
                   "coadd.execute.dispatch": 10, "coadd.execute.sync": 10}
    assert sum(got.values()) == 400


def test_idle_by_span_sums_to_the_idle_time():
    idle = ps.idle_by_span(SUMMARY, PROGRAM, 0, 1400)
    busy = tr.busy_seconds(SUMMARY, 0, 1400)
    assert sum(idle.values()) == pytest.approx(1400e-9 - busy)
    assert idle["coadd.execute.fetch"] == pytest.approx((120 + 90) * 1e-9)
    # 1290-1400: after the last fetch, no program span.
    assert idle["none"] == pytest.approx((30 + 110) * 1e-9)


def test_idle_gaps_named_for_the_innermost_program_span():
    gaps = ps.idle_gaps(SUMMARY, PROGRAM, 0, 1400)
    # [500, 900): fetch 120 ns against grid 118; [1200, 1400): fetch 90
    # and none 110 -> fetch, the program span that covers most of it;
    # [0, 100): grid 52 of it.
    assert [g[0] for g in gaps] == ["coadd.execute.fetch", "coadd.execute.fetch",
                                    "coadd.execute.grid"]
    assert [g[1] for g in gaps] == pytest.approx([400e-9, 200e-9, 100e-9])


def test_idle_gaps_without_program_spans_is_the_benchmarks_answer():
    assert ps.idle_gaps(SUMMARY, [], 0, 1400) == tr.idle_gaps(SUMMARY, 0, 1400)
    only_plan = [["coadd.plan", 650, 50, {}]]
    # The gap no program span covers keeps the benchmark's name for it.
    got = ps.idle_gaps(SUMMARY, only_plan, 0, 1400)
    assert [g[0] for g in got] == ["coadd.plan", "bench.execute", "bench.execute"]


def test_read_keeps_the_programs_spans_and_stats(tiny_root, tmp_path):
    """A CPU trace of one query of the tiny engine holds the execute spans
    with their byte counts; the benchmark's own spans are not among them."""
    import jax

    from bench import archive, harness

    cfg = json.loads(
        (tiny_root / "bench" / "configs" / "sdss_s82_resident.json").read_text())
    arch = archive.make_archive(cfg, 7)
    eng = harness.build_engine(arch, cfg)
    from repro.core import CoaddQuery

    q = CoaddQuery(band=archive.BANDS[int(arch.band[0])], ra_bounds=tuple(arch.bounds[0][:2]),
                   dec_bounds=tuple(arch.bounds[0][2:]), npix=16)
    assert eng.run(q, "sql_structured").stats.files_contributing > 0
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.execute"):
        res = eng.run(q, "sql_structured")
    jax.profiler.stop_trace()
    got = ps.read(str(tmp_path))
    names = [s[0] for s in got]
    assert names[0] == "coadd.plan" and "coadd.execute.fetch" in names
    assert not any(n.startswith("bench.") for n in names)
    lo, hi = got[0][1], got[-1][1] + got[-1][2] + 1
    assert ps.transfer_mb(got, lo, hi) * 1e6 == \
        res.stats.h2d_bytes + res.stats.d2h_bytes
    assert tr.summarize(str(tmp_path))["spans"][0][0] == "bench.execute"


# ----- a traced v5e run of s82_stack with the program's spans ----------------

DATA = Path(__file__).parent / "data"


@pytest.fixture(scope="module")
def v5e(tmp_path_factory):
    """(summary, program spans, what the run printed) of the traced
    window of one TPU v5 lite run of ``s82_stack``."""
    root = tmp_path_factory.mktemp("v5e_spans")
    d = root / "plugins" / "profile" / "run"
    d.mkdir(parents=True)
    with gzip.open(DATA / "s82_stack_v5e_spans.xplane.pb.gz", "rb") as f, \
            open(d / "t.xplane.pb", "wb") as g:
        shutil.copyfileobj(f, g)
    printed = json.loads((DATA / "s82_stack_v5e_spans.json").read_text())
    return tr.summarize(str(root)), ps.read(str(root)), printed


def test_v5e_spans_give_back_what_the_run_printed(v5e):
    s, sp, printed = v5e
    lo, hi = tr.window(s)
    assert (hi - lo) / 1e9 == pytest.approx(printed["trace_window_s"], abs=1e-9)
    assert tr.busy_seconds(s, lo, hi) == pytest.approx(printed["busy_s"], abs=1e-9)
    for key, name in (("prepare_ms", "coadd.execute.prepare"),
                      ("fetch_ms", "coadd.execute.fetch")):
        assert ps.median_ms(sp, name, lo, hi) == pytest.approx(printed[key], rel=1e-12)
    assert ps.transfer_mb(sp, lo, hi) == pytest.approx(printed["transfer_mb"], rel=1e-12)
    assert ps.transfer_mb(sp, lo, hi) == pytest.approx(16.78, abs=0.01)
    gaps = ps.idle_gaps(s, sp, lo, hi)
    assert [g[0] for g in gaps] == [g[0] for g in printed["idle_gaps"]]
    assert all(g[0].startswith("coadd.") for g in gaps)
    assert [g[1] for g in gaps] == pytest.approx([g[1] for g in printed["idle_gaps"]])
    # Without the program's spans the same trace names its gaps as before.
    assert {g[0] for g in tr.idle_gaps(s, lo, hi)} == {"bench.execute"}


def test_v5e_idle_time_lies_under_program_spans(v5e):
    s, sp, printed = v5e
    lo, hi = tr.window(s)
    idle = ps.idle_by_span(s, sp, lo, hi)
    assert idle == pytest.approx(printed["idle_by_span"])
    assert idle.get("none", 0.0) <= 0.1 * sum(idle.values())


def test_v5e_scans_run_inside_their_querys_dispatch_to_sync(v5e):
    """The shared clock: every scan program on the device lies inside the
    host interval from its query's dispatch to the end of its sync, to
    within 1 ms."""
    s, sp, printed = v5e
    lo, hi = tr.window(s)
    dispatch = [x for x in sp if x[0] == "coadd.execute.dispatch" and lo <= x[1] < hi]
    sync = [x for x in sp if x[0] == "coadd.execute.sync" and lo <= x[1] < hi]
    scans = sorted((m for m in s["devices"]["/device:TPU:0"]["modules"]
                    if "coadd_scan" in m[0] and lo <= m[1] < hi),
                   key=lambda m: m[1])
    assert len(scans) == len(dispatch) == len(sync) == printed["queries"]
    for (_, t, d), disp, syn in zip(scans, dispatch, sync):
        assert disp[1] - 1_000_000 <= t
        assert t + d <= syn[1] + syn[2] + 1_000_000
