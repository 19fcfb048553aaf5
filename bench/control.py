#!/usr/bin/env python3
"""The control of a cell's check: the reference computed in bfloat16, put in
the program's place, read by the same comparison.

    python3 bench/control.py --workload s82_stack --seeds 1,2,3

For each seed it answers the queries a run would compare
(``control_sample`` of the cell's driver) with the bfloat16 reference, and
prints one JSON line with the compared numbers against the float64
reference.  Each query renders only the frames it overlaps, each as the
cell's archive holds it, so the readings are those of the cell's own size
in a few GB of host memory.  It needs no accelerator and is not part of a
benchmark run; its readings set the upper end of each limit.
"""

from __future__ import annotations

import argparse
import json
import sys
import types
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT)]


def readings(cell, seed: int, seconds: int) -> dict:
    from bench import archive, reference

    ctx = types.SimpleNamespace(cfg=cell.config, traffic=cell.traffic,
                                seed=seed, seconds=seconds)
    table = archive.make_archive(cell.config, seed, frames=[])
    out = []
    for band, box, npix in cell.driver.control_sample(ctx):
        arc = archive.make_archive(cell.config, seed,
                                   frames=table.overlapping(band, box))
        ref = reference.coadd(arc, band, box, npix)
        ctl = reference.coadd(arc, band, box, npix, lowp=True)
        out.append(reference.compare(ctl[0], ctl[1], ref))
    return reference.worst(out)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=int, default=None,
                    help="window length the run would have (default: the manifest's)")
    args = ap.parse_args(argv)
    from bench import harness

    cell = harness.Cell.load(ROOT, args.workload)
    seconds = args.seconds or json.loads(
        (ROOT / "BENCHMARK.json").read_text())["run_seconds"]
    for seed in (int(s) for s in args.seeds.split(",")):
        print(json.dumps({"workload": args.workload, "seed": seed,
                          **readings(cell, seed, seconds)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
