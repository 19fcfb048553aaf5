"""Configurations, traffic mixes, per-layer metrics and limits are files
found by name: a new cell needs new files and manifest entries, and no edit
of any file the benchmark already has."""

import json
import shutil

import pytest
from bench_tiny import MANIFEST, REPO, make_tiny_tree, run_cell

from bench import harness

@pytest.mark.parametrize("metric", MANIFEST["per_layer"], ids=lambda m: m["name"])
def test_metric_file_matches_manifest(metric):
    mod = harness.load_module(REPO / "bench" / "metrics" / f"{metric['name']}.py")
    assert (mod.NAME, mod.UNIT, mod.LAYER, mod.MOVES, mod.SOURCE) == (
        metric["name"], metric["unit"], metric["layer"], metric["moves"],
        metric["source"])
    e2e = {m["name"]: m for m in MANIFEST["end_to_end"]}
    for w in metric["workloads"]:
        assert "workloads" not in e2e[metric["moves"]] or \
            w in e2e[metric["moves"]]["workloads"]


@pytest.mark.parametrize("workload", MANIFEST["workloads"], ids=lambda w: w["name"])
def test_every_cell_loads(tiny_root, workload):
    cell = harness.Cell.load(tiny_root, workload["name"])
    assert cell.config["name"] == workload["config"]
    assert cell.config["chips"] == workload["chips"]
    for fn in ("warm", "window", "end_to_end", "sample", "control_sample"):
        assert callable(getattr(cell.driver, fn))
    assert set(cell.limits["limits"]) == {"coadd_max_rel", "coadd_rms_rel"}


def test_new_cell_is_files_only(tmp_path):
    root = make_tiny_tree(tmp_path)
    before = {p: p.read_bytes() for p in (root / "bench").rglob("*") if p.is_file()}
    bench = root / "bench"
    cfg = json.loads((bench / "configs" / "sdss_s82_resident.json").read_text())
    cfg.update(name="s82_three_bands", bands=3)
    (bench / "configs" / "s82_three_bands.json").write_text(json.dumps(cfg))
    tr = json.loads((bench / "traffic" / "stack_closed.json").read_text())
    tr.update(npix=12, compare=2)
    (bench / "traffic" / "stack_small.json").write_text(json.dumps(tr))
    shutil.copy(bench / "limits" / "s82_stack.json", bench / "limits" / "s82_small.json")
    (bench / "metrics" / "queries_done.small.py").write_text(
        'NAME = "queries_done.small"\nUNIT = "queries"\nLAYER = "executor"\n'
        'MOVES = "frames_per_s"\nSOURCE = "program_counter"\n\n\n'
        'def read(record, trace):\n    return float(len(record["queries"]))\n')
    man = json.loads((root / "BENCHMARK.json").read_text())
    man["configs"].append({"name": "s82_three_bands", "source": "https://arxiv.org/abs/1010.1015",
                           "file": "bench/configs/s82_three_bands.json",
                           "reduced": ["runs", "fields", "bands"], "why": "three bands"})
    man["workloads"].append({"name": "s82_small", "config": "s82_three_bands",
                             "traffic": "stack_small", "chips": 1, "why": "small boxes"})
    man["end_to_end"][0]["workloads"].append("s82_small")
    man["per_layer"].append({"name": "queries_done.small", "unit": "queries",
                             "better": "higher", "source": "program_counter",
                             "layer": "executor", "moves": "frames_per_s",
                             "workloads": ["s82_small"]})
    (root / "BENCHMARK.json").write_text(json.dumps(man))
    rc, line, _ = run_cell(root, "s82_small", trace=True)
    assert rc == 0 and line["correct"] is True
    assert line["metrics"]["queries_done.small"]["value"] == line["attempted"] > 0
    assert {p: p.read_bytes() for p in before} == before
