"""Both cells end to end on the CPU at tiny frame size, the Pallas lane
interpreted: the result line has the contract's shape, the check passes,
and nothing is printed under a device metric's name."""

import pytest
from bench_tiny import CELLS, MANIFEST, REPO, run_cell

COUNTERS = {m["name"] for m in MANIFEST["per_layer"]
            if m["source"] == "program_counter"}


@pytest.mark.parametrize("trace", [False, True], ids=["trace0", "trace1"])
@pytest.mark.parametrize("workload", CELLS)
def test_cell_rehearses_on_cpu(tiny_root, workload, trace):
    rc, line, err = run_cell(tiny_root, workload, trace=trace)
    assert rc == 0
    assert list(line) == ["correct", "attempted", "failed", "metrics",
                          "device", "compared"]
    assert line["correct"] is True
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["device"]["platform"] == "cpu"
    assert "busy_s" not in line["device"]
    # A CPU run reports only what the program counts.
    assert set(line["metrics"]) <= COUNTERS
    if trace:
        wanted = {m["name"] for m in MANIFEST["per_layer"]
                  if m["name"] in COUNTERS and workload in m["workloads"]}
        assert set(line["metrics"]) == wanted
    else:
        assert line["metrics"] == {}
    compared = line["compared"]
    assert set(compared) == {"coadd_max_rel", "coadd_rms_rel"}
    assert all(v["value"] <= v["limit"] for v in compared.values())
    tail = err[-len(compared):]
    assert all(t.startswith("compared ") and " limit " in t for t in tail)
    assert any(l.startswith("window:") and "0 compiles" in l for l in err)


def test_no_accelerator_no_result(tiny_root, capsys):
    from bench import harness

    rc = harness.run(CELLS[0], 1, 1, False, root=tiny_root)
    out = capsys.readouterr()
    assert rc != 0 and out.out == ""
    assert "accelerator" in out.err


def test_tree_without_program_exits_non_zero(tmp_path):
    import shutil
    import subprocess
    import sys

    shutil.copytree(REPO / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(REPO / "BENCHMARK.json", tmp_path)
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELLS[0], "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, capture_output=True, text=True, timeout=120,
        env={"PATH": "/usr/bin:/bin", "JAX_PLATFORMS": "cpu"})
    assert proc.returncode != 0 and proc.stdout == ""
