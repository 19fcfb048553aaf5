"""Median host time the planner took to locate a query's frames, from the
program's own span (``JobStats.t_locate_s``), over the window's queries."""

import statistics

NAME = "plan_ms.stack"
UNIT = "ms"
LAYER = "planner (core/plan.py, core/prefilter.py)"
MOVES = "frames_per_s"
SOURCE = "program_span"


def read(record, trace):
    ts = [q["t_locate_s"] for q in record.get("queries", []) if q["dispatched"]]
    return 1e3 * statistics.median(ts) if ts else None
