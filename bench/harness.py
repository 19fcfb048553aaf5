"""One run of one cell: set-up, the measured window, the check, the result line.

Everything that belongs to one configuration, traffic mix, driver kind,
per-layer metric or cell's limits is a file found by name:

* ``bench/configs/<config>.json`` (the manifest names the file);
* ``bench/traffic/<traffic>.json``, whose ``driver`` names
  ``bench/drivers/<driver>.py``;
* ``bench/metrics/<metric>.py``;
* ``bench/limits/<workload>.json``.

A driver module provides ``warm(ctx)``, ``window(ctx, seconds)`` (returns
the run's record), ``end_to_end(record)``, ``sample(ctx, record)`` (the
answers the check compares: ``[(band, box, npix, coadd, depth), ...]``) and
``control_sample(ctx)`` (the queries the control answers).  A metric module
provides ``read(record, trace)``, returning a number or None.
"""

from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import shutil
import sys
import tempfile
import time
import types
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


# ----- finding things by name ----------------------------------------------

def load_module(path: Path) -> types.ModuleType:
    spec = importlib.util.spec_from_file_location(
        f"bench_{path.parent.name}_{path.stem.replace('.', '_')}", path)
    if spec is None or not path.is_file():
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _named(entries, name, what):
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no {what} named {name!r} in BENCHMARK.json")


def _applies(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


@dataclasses.dataclass
class Cell:
    """A workload of the manifest with every file it names, loaded."""

    root: Path
    workload: dict
    config: dict
    traffic: dict
    driver: types.ModuleType
    limits: dict
    end_to_end: list
    per_layer: list

    @staticmethod
    def load(root: Path, name: str) -> "Cell":
        root = Path(root)
        manifest = json.loads((root / "BENCHMARK.json").read_text())
        wl = _named(manifest["workloads"], name, "workload")
        cfg_entry = _named(manifest["configs"], wl["config"], "config")
        config = json.loads((root / cfg_entry["file"]).read_text())
        bench = root / "bench"
        traffic = json.loads((bench / "traffic" / f"{wl['traffic']}.json").read_text())
        driver = load_module(bench / "drivers" / f"{traffic['driver']}.py")
        limits = json.loads((bench / "limits" / f"{name}.json").read_text())
        e2e = [m for m in manifest["end_to_end"] if _applies(m, name)]
        layer = [m for m in manifest["per_layer"] if _applies(m, name)]
        return Cell(root, wl, config, traffic, driver, limits, e2e, layer)

    def metric_module(self, name: str) -> types.ModuleType:
        return load_module(self.root / "bench" / "metrics" / f"{name}.py")


# ----- compile accounting (as the program's bring-up check does it) ---------

class CompileClock:
    """Backend compiles and persistent-cache loads, as JAX reports them."""

    def __init__(self, jax):
        self.compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def mark(self):
        return self.compiles, self.cache_hits


# ----- the program under test ------------------------------------------------

def build_engine(archive, cfg: dict):
    """The program's engine over the benchmark's archive."""
    from repro.core import CoaddEngine, SurveyConfig
    from repro.core.geometry import WCS
    from repro.core.survey import Survey, SurveyImage

    from bench.archive import Geometry

    geo = Geometry.of(cfg)
    scfg = SurveyConfig(
        n_runs=cfg["runs"], n_camcols=cfg["camcols"], n_bands=cfg["bands"],
        n_fields=cfg["fields"], height=cfg["height"], width=cfg["width"],
        ra_start=cfg["ra_start"], field_ra_deg=geo.field_deg,
        camcol_dec_deg=geo.camcol_deg, dec_center=cfg["dec_center"],
        psf_sigma_px=cfg["psf_sigma_px"], psf_stamps=False,
        n_sources=cfg["n_sources"])
    images = []
    for i in range(len(archive)):
        v = archive.wcs[i]
        images.append(SurveyImage(
            image_id=i, run=int(archive.run[i]), camcol=int(archive.camcol[i]),
            band_id=int(archive.band[i]), field=int(archive.field[i]),
            t_obs=float(archive.run[i] * 100 + archive.field[i]),
            wcs=WCS(crval=(v[0], v[1]), crpix=(v[2], v[3]),
                    cd=((v[4], v[5]), (v[6], v[7]))),
            bounds=tuple(float(b) for b in archive.bounds[i]),
            pixels=archive.pixels[i], psf_sigma=float(archive.seeing[i])))
    empty = archive.pixels[:0, 0, 0].astype("float64")
    survey = Survey(scfg, images, empty, empty, empty)
    eng = cfg["engine"]
    return CoaddEngine(survey, pack_capacity=eng["pack_capacity"],
                       device_budget_bytes=eng["device_budget_bytes"])


# ----- one run ----------------------------------------------------------------

def _log(err, msg):
    print(msg, file=err, flush=True)


def _device_peak_bytes(jax):
    stats = [d.memory_stats() or {} for d in jax.local_devices()]
    return max(int(s.get("peak_bytes_in_use", 0)) for s in stats)


def setup(cell: Cell, seed: int, seconds: int, jax, clock: CompileClock, err):
    """Archive, engine, upload and warm-up of one run.  Returns the run's
    context (``ctx.setup_split`` holds each phase's seconds) and the compile
    counts after warm-up."""
    from bench import archive as archive_mod

    ctx = types.SimpleNamespace(
        cfg=cell.config, traffic=cell.traffic, seed=seed, seconds=seconds,
        span=jax.profiler.TraceAnnotation, log=lambda m: _log(err, m))
    split = {}
    t = time.perf_counter()
    ctx.archive = archive_mod.make_archive(cell.config, seed)
    split["synthesis"] = time.perf_counter() - t
    t = time.perf_counter()
    ctx.engine = build_engine(ctx.archive, cell.config)
    layout = cell.config["engine"]["layout"]
    ctx.engine.dataset(layout)
    split["packing"] = time.perf_counter() - t
    t = time.perf_counter()
    if cell.config["engine"]["device_budget_bytes"] is None:
        jax.block_until_ready(ctx.engine.device_dataset(layout).pixels)
    split["upload"] = time.perf_counter() - t
    t = time.perf_counter()
    c0 = clock.mark()
    cell.driver.warm(ctx)
    split["warm-up"] = time.perf_counter() - t
    c1 = clock.mark()
    ctx.setup_split = split
    _log(err, f"archive: {len(ctx.archive)} frames, {ctx.archive.nbytes} bytes; "
              f"warm-up {c1[0] - c0[0]} compiles, {c1[1] - c0[1]} cache loads")
    return ctx, c1


def run(workload: str, seed: int, seconds: int, trace: bool, *,
        root: Path = ROOT, t_start: float | None = None,
        require_accelerator: bool = True, out=None, err=None) -> int:
    """Run one cell once and print its result line; returns the exit code.

    ``require_accelerator=False`` is for the CPU rehearsal in the tests: the
    run goes through, and only what the program counts is reported.
    """
    t_start = time.perf_counter() if t_start is None else t_start
    out = out or sys.stdout
    err = err or sys.stderr
    cell = Cell.load(root, workload)
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if require_accelerator and (platform == "cpu"
                                or len(devices) < cell.workload["chips"]):
        _log(err, f"bench: needs {cell.workload['chips']} accelerator chip(s); "
                  f"JAX found {len(devices)} {platform} device(s); nothing run")
        return 3
    if require_accelerator:
        from repro.compile_cache import enable_compile_cache

        _log(err, f"compile cache: {enable_compile_cache()}")
    clock = CompileClock(jax)

    from bench import reference

    ctx, c1 = setup(cell, seed, seconds, jax, clock, err)
    setup_s = time.perf_counter() - t_start
    _log(err, f"setup: {setup_s:.3f}s = " + " + ".join(
        f"{k} {v:.3f}s" for k, v in ctx.setup_split.items())
        + f" + start {setup_s - sum(ctx.setup_split.values()):.3f}s")

    log_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    if trace:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            record = cell.driver.window(ctx, seconds)
    finally:
        if trace:
            jax.profiler.stop_trace()
    c2 = clock.mark()
    _log(err, f"window: {record['window_s']:.3f}s, {record['attempted']} "
              f"attempted, {record['failed']} failed; {c2[0] - c1[0]} compiles "
              f"and {c2[1] - c1[1]} cache loads inside the window")
    peak = _device_peak_bytes(jax)
    record["device_kind"] = devices[0].device_kind
    record["capacity"] = cell.config["engine"]["pack_capacity"]

    summary = None
    if trace:
        from bench import trace_reduce

        summary = trace_reduce.summarize(log_dir)
        shutil.rmtree(log_dir, ignore_errors=True)

    # The check: the answers the window produced against the reference,
    # once the program's state is freed.
    answers = cell.driver.sample(ctx, record)
    ctx.engine = None
    record.pop("results", None)
    gc.collect()
    t = time.perf_counter()
    readings = [reference.compare(c, d, reference.coadd(ctx.archive, b, box, n))
                for b, box, n, c, d in answers]
    ref_s = time.perf_counter() - t
    worst = reference.worst(readings) if readings else {
        k: float("inf") for k in reference.COMPARED}
    limits = cell.limits["limits"]
    correct = bool(readings) and all(worst[k] <= limits[k] for k in reference.COMPARED)
    _log(err, f"reference: {len(readings)} answers in {ref_s:.3f}s; depth "
              f"mismatches outside ambiguous pixels "
              f"{[r['depth_mismatch'] for r in readings]}; ambiguous share "
              f"{max((r['ambiguous_share'] for r in readings), default=0):.3e}")

    device = {"platform": platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    units = {m["name"]: m["unit"] for m in cell.end_to_end + cell.per_layer}
    metrics, breakdown = {}, None
    on_chip = platform != "cpu"
    if not trace:
        values = dict(cell.driver.end_to_end(record))
        values["hbm_peak_gb"] = peak / 1e9
        values["setup_s"] = setup_s
        for m in cell.end_to_end:
            if on_chip and values.get(m["name"]) is not None:
                metrics[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}
    else:
        from bench import trace_reduce

        lo, hi = trace_reduce.window(summary)
        tr = {"summary": summary, "lo": lo, "hi": hi}
        for m in cell.per_layer:
            if not on_chip and m["source"] != "program_counter":
                continue
            v = cell.metric_module(m["name"]).read(record, tr)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": units[m["name"]]}
        if on_chip and summary["devices"]:
            device["busy_s"] = trace_reduce.busy_seconds(summary, lo, hi)
            device["window_s"] = (hi - lo) / 1e9
            breakdown = {
                "device_ops": trace_reduce.top_ops(summary, lo, hi),
                "idle_gaps": trace_reduce.idle_gaps(summary, lo, hi)}
    line = {"correct": correct, "attempted": record["attempted"],
            "failed": record["failed"], "metrics": metrics, "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    # A non-finite answer reads as the largest float, so the line stays JSON.
    worst = {k: min(v, sys.float_info.max) for k, v in worst.items()}
    line["compared"] = {k: {"value": worst[k], "limit": limits[k]}
                        for k in reference.COMPARED}
    for k in reference.COMPARED:
        _log(err, f"compared {k} {worst[k]!r} limit {limits[k]!r}")
    print(json.dumps(line), file=out, flush=True)
    return 0
