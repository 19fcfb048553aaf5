"""The chip smoke's CPU rehearsal, its off-chip refusal, and where the
entry points keep JAX's persistent compilation cache."""
import importlib.util
import json
import sys
from pathlib import Path

import jax
import pytest

from repro import compile_cache

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    sys.modules["chip_smoke"] = mod   # its dataclasses resolve through it
    spec.loader.exec_module(mod)
    return mod


def test_rehearse_cpu_runs_every_phase(chip_smoke, capsys):
    assert chip_smoke.main(["--rehearse-cpu"]) == 0
    lines = capsys.readouterr().out.strip().splitlines()
    for phase in ("a_resident", "b_psf_matched", "c_service", "d_streaming",
                  "e_kernel_lane"):
        assert any(ln.startswith(f"phase {phase}: ok") for ln in lines), phase
    assert json.loads(lines[-1]) == {
        "ok": True,
        "device": {"platform": "cpu", "kind": jax.devices()[0].device_kind,
                   "count": len(jax.devices())},
    }


def test_refuses_without_a_tpu(chip_smoke, capsys):
    assert jax.default_backend() != "tpu"
    assert chip_smoke.main([]) != 0
    assert capsys.readouterr().out == ""     # no result line, no phases


def test_compile_cache_directory(monkeypatch):
    saved = {k: getattr(jax.config, k) for k in (
        "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs")}
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
        assert compile_cache.enable_compile_cache() == "/elsewhere/cache"
        # Left to JAX: no directory of its own set in code.
        assert jax.config.jax_compilation_cache_dir == \
            saved["jax_compilation_cache_dir"]
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        got = compile_cache.enable_compile_cache()
        assert got == str(ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == got
    finally:
        for k, v in saved.items():
            jax.config.update(k, v)
