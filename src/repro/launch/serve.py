"""Coadd-serving CLI: demo and seeded concurrency drill for `CoaddService`.

Replaces the dormant LLM-decode driver this file used to hold: serving here
means the paper's workload — concurrent multi-tenant coadd queries through
the async front end (`repro.core.serve`, DESIGN.md §10), coalesced into the
engine's batched one-dispatch scans.

Demo:
  PYTHONPATH=src python -m repro.launch.serve --clients 16

Drill (CI `serve-smoke`): same run, then assert the serving contract —
every response bitwise-equal to a direct `engine.run`, coalesce factor
above 1, zero requests shed below the admission limit — and exit nonzero
on any violation:
  PYTHONPATH=src python -m repro.launch.serve --clients 16 --drill
"""

from __future__ import annotations

import argparse
import asyncio
import json
import time

import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.core import (
    CoaddEngine,
    CoaddQuery,
    CoaddService,
    SurveyConfig,
    make_survey,
)

DRILL_SURVEY = SurveyConfig(
    n_runs=4, n_camcols=4, n_bands=3, n_fields=6,
    height=24, width=24, n_sources=150, seed=9,
)


def drill_queries(seed: int, clients: int, pool: int):
    """Seeded multi-tenant workload: a skewed draw over a mixed query pool.

    The pool interleaves cheap quarter-degree-ish queries with full-stripe
    monsters at a different npix (so the two classes neither share a
    coalesce group nor a cost class), and clients draw from it with
    popularity skew — repeats are the realistic case the result cache and
    in-flight merging exist for.
    """
    rng = np.random.default_rng(seed)
    qs = []
    for i in range(pool):
        if i % 4 == 3:  # monster: whole footprint, larger grid
            qs.append(CoaddQuery(
                band="r", ra_bounds=(37.0, 38.5), dec_bounds=(-0.8, 0.8),
                npix=96,
            ))
        else:  # cheap: small box sliding along RA
            lo = 37.1 + 0.15 * i
            qs.append(CoaddQuery(
                band="r", ra_bounds=(lo, lo + 0.4), dec_bounds=(-0.3, 0.3),
                npix=64,
            ))
    # Zipf-ish popularity: earlier pool entries are hotter.
    w = 1.0 / np.arange(1, pool + 1)
    picks = rng.choice(pool, size=clients, p=w / w.sum())
    return [qs[int(i)] for i in picks]


async def _run_service(engine, queries, args):
    svc = CoaddService(
        engine,
        method=args.method,
        max_queue=args.max_queue,
        max_batch=args.max_batch,
    )
    # Queue the whole burst before starting the dispatcher: the recorded-
    # burst replay pattern, and what makes the drill's coalescing
    # deterministic rather than racing the first drain.
    tasks = [
        asyncio.ensure_future(svc.submit(q, tenant=f"t{i % 4}"))
        for i, q in enumerate(queries)
    ]
    while svc.queue_depth < len(queries):
        await asyncio.sleep(0.005)
    t0 = time.perf_counter()
    async with svc:
        results = await asyncio.gather(*tasks)
    wall = time.perf_counter() - t0
    return svc, results, wall


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--clients", type=int, default=16)
    ap.add_argument("--pool", type=int, default=8,
                    help="distinct queries the clients draw from")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--method", default="sql_structured")
    ap.add_argument("--max-queue", type=int, default=64)
    ap.add_argument("--max-batch", type=int, default=16)
    ap.add_argument("--drill", action="store_true",
                    help="assert the serving contract; exit 1 on violation")
    args = ap.parse_args(argv)

    enable_compile_cache()
    survey = make_survey(DRILL_SURVEY)
    engine = CoaddEngine(survey, pack_capacity=16)
    queries = drill_queries(args.seed, args.clients, args.pool)

    # Serial reference: each distinct query straight through the engine.
    serial = {}
    t0 = time.perf_counter()
    for q in queries:
        if q not in serial:
            serial[q] = engine.run(q, args.method)
    t_serial_unique = time.perf_counter() - t0

    svc, results, wall = asyncio.run(_run_service(engine, queries, args))

    snap = svc.stats.snapshot()
    mismatched = sum(
        not (np.array_equal(r.coadd, serial[q].coadd)
             and np.array_equal(r.depth, serial[q].depth))
        for q, r in zip(queries, results)
    )
    out = {
        "clients": args.clients,
        "distinct": len(serial),
        "wall_s": round(wall, 4),
        "serial_unique_s": round(t_serial_unique, 4),
        "bitwise_mismatches": mismatched,
        "stats": snap,
    }
    print(json.dumps(out, indent=1))

    if args.drill:
        failures = []
        if mismatched:
            failures.append(
                f"{mismatched}/{args.clients} responses differ bitwise "
                f"from direct engine.run"
            )
        if not svc.stats.coalesce_factor > 1.0:
            failures.append(
                f"coalesce factor {svc.stats.coalesce_factor:.2f} <= 1"
            )
        if svc.stats.shed != 0:
            failures.append(
                f"{svc.stats.shed} requests shed below the admission limit"
            )
        if svc.stats.completed != args.clients:
            failures.append(
                f"completed {svc.stats.completed} != {args.clients}"
            )
        if failures:
            for f in failures:
                print(f"DRILL FAIL: {f}")
            raise SystemExit(1)
        print(f"DRILL OK: {args.clients} clients, "
              f"{snap['dispatches']} dispatches, "
              f"coalesce {snap['coalesce_factor']}x, 0 shed, bitwise clean")
    return out


if __name__ == "__main__":
    main()
