"""Quickstart: build a synthetic Stripe-82 slice, run one coadd query.

PYTHONPATH=src python examples/quickstart.py
"""
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.core import CoaddEngine, CoaddQuery, SurveyConfig, make_survey

enable_compile_cache()
survey = make_survey(SurveyConfig(n_runs=6, n_fields=8, n_sources=200,
                                  height=24, width=24))
print(f"survey: {len(survey)} CCD frames "
      f"({survey.config.n_runs} epochs x {survey.config.n_camcols} camcols "
      f"x {survey.config.n_bands} bands x {survey.config.n_fields} fields)")

engine = CoaddEngine(survey, pack_capacity=64)
query = CoaddQuery(band="r", ra_bounds=(37.5, 38.5), dec_bounds=(-0.5, 0.5), npix=128)

result = engine.run(query, "sql_structured")
s = result.stats
print(f"method={s.method} files={s.files_considered} "
      f"contributing={s.files_contributing} packs={s.packs_touched}")
print(f"locate {s.t_locate_s*1e3:.1f} ms | map+reduce {s.t_map_reduce_s*1e3:.1f} ms")
print(f"depth: min={result.depth.min():.0f} max={result.depth.max():.0f}")
np.save("/tmp/coadd.npy", result.normalized)
print("normalized coadd saved to /tmp/coadd.npy")
