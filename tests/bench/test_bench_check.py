"""The check that decides ``correct`` fails what it must: the bfloat16
control, and a run whose timed path is broken underneath."""

import dataclasses

import numpy as np
import pytest
from bench_tiny import CELLS, REPO, run_cell

from bench import harness

@pytest.mark.parametrize("workload", CELLS)
def test_control_fails_the_limits(tiny_root, workload):
    control = harness.load_module(REPO / "bench" / "control.py")
    cell = harness.Cell.load(tiny_root, workload)
    limits = cell.limits["limits"]
    for seed in (1, 2, 2**31 + 5):
        got = control.readings(cell, seed, seconds=4)
        assert any(got[k] > limits[k] for k in limits), (seed, got)


def _frozen(res):
    """A scan that returns its state unchanged: nothing accumulated."""
    return dataclasses.replace(res, coadd=np.zeros_like(res.coadd),
                               depth=np.zeros_like(res.depth))


def _altered(res):
    """One answer altered where it is produced: the deepest pixel, 1% off."""
    c = res.coadd.copy()
    i = np.unravel_index(np.argmax(res.depth), c.shape)
    c[i] *= 1.01
    return dataclasses.replace(res, coadd=c)


def _half_gate(plan):
    """Half of the frames left out, the stack taken over the rest."""
    gate = plan.gate.copy()
    on = np.flatnonzero(gate)
    gate.flat[on[::2]] = False
    return dataclasses.replace(plan, gate=gate)


FAULTS = {
    "state_unchanged": ("result", _frozen),
    "half_left_out": ("plan", _half_gate),
    "answer_altered": ("result", _altered),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("workload", CELLS)
def test_broken_timed_path_is_not_correct(tiny_root, monkeypatch, workload, fault):
    from repro.core.engine import CoaddEngine

    where, fn = FAULTS[fault]
    execute = CoaddEngine.execute
    if where == "plan":
        monkeypatch.setattr(CoaddEngine, "execute",
                            lambda self, p: execute(self, fn(p)))
    else:
        monkeypatch.setattr(CoaddEngine, "execute",
                            lambda self, p: fn(execute(self, p)))
    rc, line, err = run_cell(tiny_root, workload)
    assert rc == 0
    assert line["correct"] is False
    assert any(v["value"] > v["limit"] for v in line["compared"].values())
