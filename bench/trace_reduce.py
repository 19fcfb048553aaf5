"""From a profiler trace to the numbers the benchmark reports.

`summarize` reads the ``.xplane.pb`` that ``jax.profiler`` writes and keeps
what the reductions need, as plain lists that JSON can hold:

* per device plane, the program events (line "XLA Modules") and the
  operation events (line "XLA Ops"), each ``[name, start_ns, duration_ns]``;
* the benchmark's own host spans (``jax.profiler.TraceAnnotation`` names
  that start with ``bench.``), each ``[name, start_ns, duration_ns]``.

The reductions are functions of that summary: the device's busy time as
the union of its operation intervals, the summed time of the programs
whose name holds a pattern, the operations that took most time, and the
longest idle gaps, each named for the host span that covered it most.
"""

from __future__ import annotations

import glob
import os
from collections import defaultdict

SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"


def summarize(log_dir: str) -> dict:
    """The summary of the one trace under ``log_dir``."""
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if len(paths) != 1:
        raise RuntimeError(f"expected one xplane under {log_dir}, found {paths}")
    data = ProfileData.from_file(paths[0])
    devices, spans = {}, []
    for plane in data.planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            lines = {ln.name: ln for ln in plane.lines}
            ev = lambda ln: [[e.name, e.start_ns, e.duration_ns] for e in ln.events]
            mods = ev(lines["XLA Modules"]) if "XLA Modules" in lines else []
            ops = ev(lines["XLA Ops"]) if "XLA Ops" in lines else mods
            if mods or ops:
                devices[plane.name] = {"modules": mods, "ops": ops}
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                spans += [[e.name, e.start_ns, e.duration_ns] for e in ln.events
                          if e.name.startswith(SPAN_PREFIX)]
    return {"devices": devices, "spans": sorted(spans, key=lambda s: s[1])}


def window(summary: dict, name: str = WINDOW_SPAN):
    """(start_ns, end_ns) of the measured window's span; where the trace
    holds no such span, the extent of the device's operations."""
    for n, t, d in summary["spans"]:
        if n == name:
            return t, t + d
    ops = [e for d in summary["devices"].values() for e in d["ops"]]
    if not ops:
        return 0, 0
    return min(t for _, t, _ in ops), max(t + d for _, t, d in ops)


def _clip(events, lo, hi):
    for name, t, d in events:
        a, b = max(t, lo), min(t + d, hi)
        if b > a:
            yield name, a, b


def union(events, lo, hi):
    """Merged [start, end) intervals of ``events`` inside [lo, hi)."""
    out = []
    for _, a, b in sorted(_clip(events, lo, hi), key=lambda e: e[1]):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def busy_seconds(summary: dict, lo, hi) -> float:
    """Seconds in which an operation ran, averaged over the devices."""
    devs = summary["devices"].values()
    if not devs:
        return 0.0
    tot = sum(b - a for d in devs for a, b in union(d["ops"], lo, hi))
    return tot / len(devs) / 1e9


def program_seconds(summary: dict, pattern: str, lo, hi) -> float:
    """Summed device seconds of the programs whose name holds ``pattern``."""
    return sum(b - a for d in summary["devices"].values()
               for name, a, b in _clip(d["modules"], lo, hi)
               if pattern in name) / 1e9


def top_ops(summary: dict, lo, hi, n: int = 10):
    """[[operation, seconds]] of the ``n`` operations that took most time."""
    tot = defaultdict(int)
    for d in summary["devices"].values():
        for name, a, b in _clip(d["ops"], lo, hi):
            tot[name] += b - a
    return [[k, v / 1e9] for k, v in
            sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(summary: dict, lo, hi, n: int = 10):
    """[[host span, seconds]] of the ``n`` longest gaps in which no operation
    ran on the first device, each named for the benchmark span (other than
    the window) that covered most of it, or "none"."""
    devs = list(summary["devices"].values())
    busy = union(devs[0]["ops"], lo, hi) if devs else []
    gaps, t = [], lo
    for a, b in busy:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if hi > t:
        gaps.append((t, hi))
    spans = [s for s in summary["spans"] if s[0] != WINDOW_SPAN]
    out = []
    for a, b in sorted(gaps, key=lambda g: g[0] - g[1])[:n]:
        cover = defaultdict(int)
        for name, sa, sb in _clip(spans, a, b):
            cover[name] += sb - sa
        label = max(cover, key=cover.get) if cover else "none"
        out.append([label, (b - a) / 1e9])
    return out
