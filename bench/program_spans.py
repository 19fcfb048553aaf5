"""The program's own host spans in a profiler trace, and what they show.

The coadd engine names its host work with ``jax.profiler.TraceAnnotation``
spans whose names start with ``coadd.`` (``repro.core.spans``): per query
``coadd.plan`` and ``coadd.execute``, the latter split into ``prepare``
(``grid``, ``compact``, ``dispatch``), ``sync`` and ``fetch``, the
transfers as ``h2d_bytes`` and ``d2h_bytes`` event stats.  `read` keeps
them from the ``.xplane.pb`` of a traced run as ``[name, start_ns,
duration_ns, {stat: value}]``, on the clock of the device planes that
`bench.trace_reduce.summarize` reads.  Nothing here imports the program:
on a tree whose program has no such spans every reading is empty and each
reduction returns None.

The reductions take that list and the window [lo, hi); "the window's
queries" are the ``coadd.execute`` spans that start inside it.
"""

from __future__ import annotations

import glob
import os
import statistics
from collections import defaultdict

from bench import trace_reduce

PREFIX = "coadd."
EXECUTE = "coadd.execute"
BYTES = ("h2d_bytes", "d2h_bytes")


def read(log_dir: str) -> list:
    """The program spans of the one trace under ``log_dir``, by start."""
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if len(paths) != 1:
        raise RuntimeError(f"expected one xplane under {log_dir}, found {paths}")
    out = []
    for plane in ProfileData.from_file(paths[0]).planes:
        if plane.name.startswith("/host:"):
            for ln in plane.lines:
                out += [[e.name, e.start_ns, e.duration_ns, dict(e.stats)]
                        for e in ln.events if e.name.startswith(PREFIX)]
    return sorted(out, key=lambda s: s[1])


def queries(spans, lo, hi):
    """Per query of the window, the ``coadd.execute.*`` spans inside its
    ``coadd.execute`` span, as lists of spans."""
    out = []
    for name, t, d, _ in spans:
        if name == EXECUTE and lo <= t < hi:
            out.append([s for s in spans if s[0].startswith(EXECUTE + ".")
                        and t <= s[1] and s[1] + s[2] <= t + d])
    return out


def median_ms(spans, name, lo, hi):
    """Median duration in ms of the spans called ``name`` that start in
    the window, or None."""
    ds = [d for n, t, d, _ in spans if n == name and lo <= t < hi]
    return statistics.median(ds) / 1e6 if ds else None


def transfer_mb(spans, lo, hi):
    """Median over the window's queries of the bytes each moved between
    host and device (``h2d_bytes`` + ``d2h_bytes`` of its spans), in MB,
    or None."""
    per_query = [sum(st.get(k, 0) for _, _, _, st in q for k in BYTES)
                 for q in queries(spans, lo, hi)]
    return statistics.median(per_query) / 1e6 if per_query else None


def _gaps(summary, lo, hi):
    devs = list(summary["devices"].values())
    busy = trace_reduce.union(devs[0]["ops"], lo, hi) if devs else []
    gaps, t = [], lo
    for a, b in busy:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if hi > t:
        gaps.append((t, hi))
    return gaps


def innermost(spans, a, b) -> dict:
    """{span name: ns} of [a, b): at each instant the covering program span
    that started last (the shorter where two start together), "none" where
    no program span covers it."""
    inside = [(t, t + d, n) for n, t, d, _ in spans if t < b and t + d > a]
    cuts = sorted({a, b} | {x for s, e, _ in inside for x in (s, e) if a < x < b})
    out = defaultdict(int)
    for x, y in zip(cuts, cuts[1:]):
        cover = [(s, -e, n) for s, e, n in inside if s <= x and e >= y]
        out[max(cover)[2] if cover else "none"] += y - x
    return dict(out)


def idle_by_span(summary, spans, lo, hi) -> dict:
    """{innermost program span or "none": seconds} of the time in the
    window in which no operation ran on the first device."""
    out = defaultdict(int)
    for a, b in _gaps(summary, lo, hi):
        for name, ns in innermost(spans, a, b).items():
            out[name] += ns
    return {k: v / 1e9 for k, v in sorted(out.items(), key=lambda kv: -kv[1])}


def idle_gaps(summary, spans, lo, hi, n: int = 10):
    """`trace_reduce.idle_gaps`, with each gap named for the innermost
    program span that covers most of it; a gap no program span covers is
    named as `trace_reduce.idle_gaps` names it."""
    out = []
    for a, b in sorted(_gaps(summary, lo, hi), key=lambda g: g[0] - g[1])[:n]:
        cover = innermost(spans, a, b)
        cover.pop("none", None)
        if cover:
            out.append([max(cover, key=cover.get), (b - a) / 1e9])
        else:
            out += trace_reduce.idle_gaps(summary, a, b, n=1)
    return out
