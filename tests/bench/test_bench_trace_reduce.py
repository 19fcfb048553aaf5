"""The trace reduction: busy and idle union, per-program device time, and
gaps named for the benchmark's host spans.  The arithmetic is checked on a
small trace written out by hand in the form `summarize` gives; the reading
of a profiler trace, on the trace of a whole traced run of ``s82_stack`` on
one TPU v5 lite (``data/s82_stack_v5e.xplane.pb.gz``), against the numbers
that run printed."""

import gzip
import shutil
from pathlib import Path

import pytest

from bench import trace_reduce as tr

# Times in ns.  Device ops overlap (a fusion inside a while body), programs
# run back to back with a gap in which the host planned the next query.
SMALL = {
    "devices": {"/device:TPU:0": {
        "modules": [["jit__coadd_scan_sparse(7)", 100, 400],
                    ["jit_convert_element_type(3)", 600, 50],
                    ["jit__coadd_scan_sparse(7)", 900, 300]],
        "ops": [["while.1", 100, 400], ["fusion.2", 150, 100],
                ["convert.3", 600, 50], ["while.1", 900, 300],
                ["gather.4", 1150, 100]],
    }},
    "spans": [["bench.window", 0, 1400], ["bench.execute", 90, 520],
              ["bench.plan", 660, 230], ["bench.execute", 890, 310]],
}


def test_window_and_union():
    lo, hi = tr.window(SMALL)
    assert (lo, hi) == (0, 1400)
    ops = SMALL["devices"]["/device:TPU:0"]["ops"]
    assert tr.union(ops, lo, hi) == [[100, 500], [600, 650], [900, 1250]]
    assert tr.busy_seconds(SMALL, lo, hi) == pytest.approx((400 + 50 + 350) / 1e9)
    # Clipped to a narrower window.
    assert tr.busy_seconds(SMALL, 200, 1000) == pytest.approx((300 + 50 + 100) / 1e9)


def test_program_seconds():
    assert tr.program_seconds(SMALL, "coadd_scan", 0, 1400) == pytest.approx(700 / 1e9)
    assert tr.program_seconds(SMALL, "coadd_scan", 0, 1000) == pytest.approx(500 / 1e9)
    assert tr.program_seconds(SMALL, "no_such_program", 0, 1400) == 0.0


def test_top_ops():
    top = tr.top_ops(SMALL, 0, 1400, n=2)
    assert top == [["while.1", pytest.approx(700 / 1e9)],
                   ["fusion.2", pytest.approx(100 / 1e9)]]


def test_idle_gaps_named_for_host_spans():
    gaps = tr.idle_gaps(SMALL, 0, 1400)
    # [0,100): execute covers 10 ns, nothing else -> execute; [500,600):
    # execute 110 ns; [650,900): plan covers 230 of 250; [1250,1400): none.
    assert [g[0] for g in gaps] == ["bench.plan", "none", "bench.execute",
                                    "bench.execute"]
    assert [g[1] for g in gaps] == pytest.approx([250e-9, 150e-9, 100e-9, 100e-9])


def test_window_without_its_span_is_the_device_extent():
    no_span = {"devices": SMALL["devices"], "spans": SMALL["spans"][1:]}
    assert tr.window(no_span) == (100, 1250)
    assert tr.window({"devices": {}, "spans": []}) == (0, 0)


def test_no_device_no_numbers():
    empty = {"devices": {}, "spans": [["bench.window", 0, 10]]}
    assert tr.busy_seconds(empty, 0, 10) == 0.0
    assert tr.program_seconds(empty, "coadd_scan", 0, 10) == 0.0
    assert tr.idle_gaps(empty, 0, 10) == [["none", pytest.approx(1e-8)]]



def test_summarize_reads_a_profiler_trace(tmp_path):
    """On the CPU the trace holds the benchmark's host spans and no device
    plane, so every device number is silent."""
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: jnp.sin(x) @ x)
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.window"):
        with jax.profiler.TraceAnnotation("bench.execute"):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    s = tr.summarize(str(tmp_path))
    names = [n for n, _, _ in s["spans"]]
    assert names[:2] == ["bench.window", "bench.execute"]
    lo, hi = tr.window(s)
    assert hi > lo
    assert s["devices"] == {} and tr.busy_seconds(s, lo, hi) == 0.0


def _v5e_trace(tmp_path):
    d = tmp_path / "plugins" / "profile" / "run"
    d.mkdir(parents=True)
    src = Path(__file__).parent / "data" / "s82_stack_v5e.xplane.pb.gz"
    with gzip.open(src, "rb") as f, open(d / "t.xplane.pb", "wb") as g:
        shutil.copyfileobj(f, g)
    return tr.summarize(str(tmp_path))


def test_summarize_reads_a_v5e_trace(tmp_path):
    """The device plane, its program and operation lines and the host spans
    are found in a real TPU trace, and reduce to what that run printed:
    window_s 63.593614657, busy_s 60.047091811, idle share 5.5768536906%."""
    from bench import harness

    s = _v5e_trace(tmp_path)
    assert list(s["devices"]) == ["/device:TPU:0"]
    dev = s["devices"]["/device:TPU:0"]
    assert len(dev["modules"]) == 16
    assert all(n.startswith("jit__coadd_scan_sparse(") for n, _, _ in dev["modules"])
    assert len(dev["ops"]) > len(dev["modules"])
    names = [n for n, _, _ in s["spans"]]
    assert names.count("bench.window") == 1
    assert names.count("bench.plan") == names.count("bench.execute") == 16
    lo, hi = tr.window(s)
    assert (hi - lo) / 1e9 == pytest.approx(63.593614657, abs=1e-9)
    assert tr.busy_seconds(s, lo, hi) == pytest.approx(60.047091811, abs=1e-9)
    scan = tr.program_seconds(s, "coadd_scan", lo, hi)
    assert scan == pytest.approx(sum(d for _, _, d in dev["modules"]) / 1e9)
    idle = harness.load_module(Path(__file__).parents[2] / "bench" / "metrics"
                               / "device_idle_share.stack.py")
    share = idle.read({}, {"summary": s, "lo": lo, "hi": hi})
    assert share == pytest.approx(5.576853690623828, rel=1e-9)
    # Between queries the device waits on the host's execute call (the
    # copy back and the next dispatch); the longest gaps are named so.
    gaps = tr.idle_gaps(s, lo, hi)
    assert len(gaps) == 10 and {g[0] for g in gaps} == {"bench.execute"}
    top = tr.top_ops(s, lo, hi)
    assert top[0][0].startswith("%while") and top[0][1] <= (hi - lo) / 1e9

