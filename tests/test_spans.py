"""The engine's host spans: one ``plan`` + ``execute`` under the profiler
gives the ``coadd.*`` spans with their nesting and byte counts, and the
counts and timings in `JobStats` are the spans' own readings.  The spans
add no device sync or host transfer to ``execute``."""

import sys
from pathlib import Path

import jax
import numpy as np
import pytest

REPO = Path(__file__).resolve().parents[1]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from bench import program_spans  # noqa: E402
from repro.core import CoaddEngine, CoaddQuery, SurveyConfig, make_survey  # noqa: E402
from repro.core import engine as engine_mod  # noqa: E402
from repro.core import spans  # noqa: E402

NPIX = 24
QUERY = CoaddQuery(band="r", ra_bounds=(37.3, 37.9), dec_bounds=(-0.5, 0.3),
                   npix=NPIX)

# Each span's parent: the smallest span that encloses it.
PARENT = {
    "coadd.plan": None,
    "coadd.plan.locate": "coadd.plan",
    "coadd.execute": None,
    "coadd.execute.prepare": "coadd.execute",
    "coadd.execute.grid": "coadd.execute.prepare",
    "coadd.execute.compact": "coadd.execute.prepare",
    "coadd.execute.dispatch": "coadd.execute.prepare",
    "coadd.execute.sync": "coadd.execute",
    "coadd.execute.fetch": "coadd.execute",
}


@pytest.fixture(scope="module")
def engine():
    sv = make_survey(SurveyConfig(n_runs=2, n_fields=4, n_sources=60,
                                  height=20, width=20))
    return CoaddEngine(sv, pack_capacity=16)


def _parent(s, all_spans):
    _, t, d, _ = s
    outer = [o for o in all_spans if o is not s
             and o[1] <= t and t + d <= o[1] + o[2]]
    return min(outer, key=lambda o: o[2])[0] if outer else None


@pytest.mark.parametrize("reduce", ["mean", "clipped"])
def test_spans_nest_and_carry_the_counts(engine, reduce, tmp_path, monkeypatch):
    engine.run(QUERY, "sql_structured", reduce=reduce)  # compile outside the trace
    made = {}

    class Recorded(spans.span):
        __slots__ = ()

        def __init__(self, name, **args):
            super().__init__(name, **args)
            made[name] = self

    monkeypatch.setattr(engine_mod, "span", Recorded)
    jax.profiler.start_trace(str(tmp_path))
    plan = engine.plan(QUERY, "sql_structured", reduce)
    res = engine.execute(plan)
    jax.profiler.stop_trace()
    got = program_spans.read(str(tmp_path))

    names = [s[0] for s in got]
    assert sorted(names) == sorted(PARENT)
    assert {s[0]: _parent(s, got) for s in got} == PARENT
    start = {s[0]: s[1] for s in got}
    assert (start["coadd.execute.grid"] < start["coadd.execute.compact"]
            < start["coadd.execute.dispatch"] < start["coadd.execute.sync"]
            < start["coadd.execute.fetch"])

    st = res.stats
    args = {s[0]: s[3] for s in got}
    assert args["coadd.execute.grid"]["h2d_bytes"] == 2 * 4 * NPIX**2
    assert (args["coadd.execute.grid"]["h2d_bytes"]
            + args["coadd.execute.compact"]["h2d_bytes"]) == st.h2d_bytes
    assert args["coadd.execute.fetch"]["d2h_bytes"] == st.d2h_bytes
    assert st.d2h_bytes == 2 * 4 * NPIX**2 + 2 * 4
    lo, hi = got[0][1], got[-1][1] + got[-1][2] + 1
    assert program_spans.transfer_mb(got, lo, hi) == (st.h2d_bytes + st.d2h_bytes) / 1e6

    assert st.t_locate_s == plan.t_locate_s == made["plan.locate"].seconds
    assert st.t_map_reduce_s == (made["execute.dispatch"].seconds
                                 + made["execute.sync"].seconds)
    dur = {s[0]: s[2] for s in got}
    assert abs(dur["coadd.plan.locate"] / 1e9 - st.t_locate_s) < 1e-3


def test_execute_syncs_once_and_copies_its_answer_once(engine, monkeypatch):
    """One ``block_until_ready``, two scalar reads, two array copies."""
    from jaxlib._jax import ArrayImpl

    plan = engine.plan(QUERY, "sql_structured")
    engine.execute(plan)
    calls = {}

    def counted(method):
        orig = getattr(ArrayImpl, method)

        def wrapper(self, *a, **k):
            calls[method] = calls.get(method, 0) + 1
            return orig(self, *a, **k)
        monkeypatch.setattr(ArrayImpl, method, wrapper)

    # On the CPU numpy reads an array through the buffer protocol; on an
    # accelerator through ``__array__``: count both as copies.
    for m in ("block_until_ready", "__int__", "__buffer__", "__array__"):
        counted(m)
    res = engine.execute(plan)
    monkeypatch.undo()
    assert calls.get("block_until_ready") == 1
    assert calls.get("__int__") == 2
    assert calls.get("__buffer__", 0) + calls.get("__array__", 0) == 2
    assert isinstance(res.coadd, np.ndarray) and isinstance(res.depth, np.ndarray)


def test_span_times_itself_outside_a_trace():
    with spans.span("test", a=1) as s:
        s.set(b=2)
    assert s.seconds > 0
