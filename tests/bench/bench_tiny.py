"""A tiny copy of the benchmark tree for CPU rehearsals.

The copy keeps every file of ``bench/`` and ``BENCHMARK.json`` as they are,
except that the configurations render 48x35-pixel frames (the same 960
frames, sky geometry scaled down) and the traffic asks for small outputs,
so a whole run takes seconds on the CPU.
"""

import io
import json
import shutil
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
for p in (str(REPO / "src"), str(REPO)):
    if p not in sys.path:
        sys.path.insert(0, p)

TINY_FRAME = {"height": 48, "width": 35, "n_sources": 100}
TINY_NPIX = {"stack_closed": 16}


MANIFEST = json.loads((REPO / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in MANIFEST["workloads"]]


def make_tiny_tree(dest: Path) -> Path:
    shutil.copytree(REPO / "bench", dest / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (dest / "BENCHMARK.json").write_text(json.dumps(MANIFEST))
    for p in (dest / "bench" / "configs").glob("*.json"):
        cfg = json.loads(p.read_text())
        cfg.update(TINY_FRAME)
        p.write_text(json.dumps(cfg))
    for name, npix in TINY_NPIX.items():
        p = dest / "bench" / "traffic" / f"{name}.json"
        tr = json.loads(p.read_text())
        tr["npix"] = npix
        p.write_text(json.dumps(tr))
    return dest


def run_cell(root, workload, seed=2**31 + 11, seconds=1, trace=False):
    """One CPU rehearsal run: (exit code, result line or None, stderr lines)."""
    from bench import harness

    out, err = io.StringIO(), io.StringIO()
    rc = harness.run(workload, seed, seconds, trace, root=root,
                     require_accelerator=False, out=out, err=err)
    lines = out.getvalue().strip().splitlines()
    return rc, (json.loads(lines[-1]) if lines else None), err.getvalue().splitlines()
