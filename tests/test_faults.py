"""Chaos drills for the streaming fault domain (DESIGN.md §8).

The paper's §3 premise — failures are the norm — demands that a streaming
query survive upload failures, poisoned inputs, stragglers, and mid-query
kills without changing its answer.  Every drill here injects deterministic
faults at the engine's real seams (`ChaosInjector`) on a 4x-oversubscribed
archive and asserts *bitwise* parity with the fault-free run whenever
``on_fault="retry"`` heals, exact accounting when ``"quarantine"`` completes
partial, and journal-replay-only resumption after a kill.
"""
import numpy as np
import pytest

from repro.core import (
    ChaosInjector,
    CoaddEngine,
    CoaddQuery,
    DeterminismError,
    FaultSchedule,
    METHODS,
    PoisonSpec,
    PoisonedChunkError,
    QueryKilled,
    ResidencyManager,
    SurveyConfig,
    TransientFault,
    WindowTracker,
    classify,
    make_survey,
    window_schedule,
)
from repro.core.jobtracker import partial_digest


@pytest.fixture(scope="module")
def survey():
    return make_survey(SurveyConfig(n_runs=2, n_fields=4, n_sources=60,
                                    height=16, width=16))


QUERY = CoaddQuery(band="r", ra_bounds=(37.2, 37.8), dec_bounds=(-0.5, 0.3),
                   npix=32)

# Fault-free streaming results, shared across the matrix: one per
# (method, chunk_packs).  Parity must be bitwise — clean and faulted runs
# execute the identical jitted programs in the identical window order.
_REFS = {}


def _chaos(survey, injector=None, chunk_packs=2, **kw):
    """A 4x-oversubscribed streaming engine with fast-backoff fault handling."""
    probe = CoaddEngine(survey, pack_capacity=8)
    ds = probe.exec_dataset("structured")[0]
    budget = max(ds.chunk_nbytes(0, ds.n_packs) // 4, 1)
    return CoaddEngine(survey, pack_capacity=8, device_budget_bytes=budget,
                       stream_chunk_packs=chunk_packs, fault_backoff_s=1e-4,
                       fault_injector=injector, **kw)


def _reference(survey, method, chunk_packs=2):
    key = (method, chunk_packs)
    if key not in _REFS:
        _REFS[key] = _chaos(survey, chunk_packs=chunk_packs).run(QUERY, method)
    return _REFS[key]


def _query_shape(survey, method, chunk_packs=2):
    """(gated global packs, n_windows) of the clean query, for fault aiming."""
    eng = _chaos(survey, chunk_packs=chunk_packs)
    plan = eng.plan(QUERY, method)
    gate = eng._exec_gate(plan)
    exec_ds, _ = eng.exec_dataset(plan.layout)
    windows = eng._stream_windows(exec_ds, gate.any(axis=1))
    return np.nonzero(gate.any(axis=1))[0], len(windows)


# ----- the 6-method chaos matrix -------------------------------------------

@pytest.mark.parametrize("method", METHODS)
def test_upload_failure_retries_to_bitwise_parity(survey, method):
    ref = _reference(survey, method)
    inj = ChaosInjector(FaultSchedule(upload_fail_ordinals=(0,)))
    r = _chaos(survey, injector=inj).run(QUERY, method)
    assert inj.injected["upload_fail"] == 1
    assert r.stats.retries >= 1
    assert not r.stats.partial
    np.testing.assert_array_equal(r.coadd, ref.coadd)
    np.testing.assert_array_equal(r.depth, ref.depth)


@pytest.mark.parametrize("method", METHODS)
def test_poisoned_chunk_retries_to_bitwise_parity(survey, method):
    ref = _reference(survey, method)
    packs, _ = _query_shape(survey, method)
    inj = ChaosInjector(FaultSchedule(
        poison=(PoisonSpec(pack=int(packs[0]), mode="nan", count=1),)
    ))
    r = _chaos(survey, injector=inj).run(QUERY, method)
    assert inj.injected["poison"] >= 1
    assert r.stats.retries >= 1
    np.testing.assert_array_equal(r.coadd, ref.coadd)
    np.testing.assert_array_equal(r.depth, ref.depth)


@pytest.mark.parametrize("method", METHODS)
def test_straggler_speculation_bitwise_parity(survey, method):
    # Single-pack chunks force enough windows for a duration median.
    ref = _reference(survey, method, chunk_packs=1)
    _, n_windows = _query_shape(survey, method, chunk_packs=1)
    assert n_windows >= 3
    inj = ChaosInjector(FaultSchedule(slow_windows={n_windows - 1: 0.05}))
    r = _chaos(survey, injector=inj, chunk_packs=1,
               straggler_factor=3.0).run(QUERY, method)
    assert inj.injected["slow"] == 1
    assert r.stats.speculative_windows >= 1
    np.testing.assert_array_equal(r.coadd, ref.coadd)
    np.testing.assert_array_equal(r.depth, ref.depth)


@pytest.mark.parametrize("method", METHODS)
def test_kill_and_resume_replays_only_missing_windows(survey, method):
    ref = _reference(survey, method)
    _, n_windows = _query_shape(survey, method)
    assert n_windows >= 2
    inj = ChaosInjector(FaultSchedule(kill_after_windows=1))
    eng = _chaos(survey, injector=inj)
    with pytest.raises(QueryKilled):
        eng.run(QUERY, method)
    assert len(eng._journals) == 1  # the killed query's journal survives
    r = eng.run(QUERY, method)      # injector fired once; resume runs clean
    # Journal-hit accounting: exactly the windows finished before the kill
    # replay from the journal, the rest re-execute.
    assert r.stats.resumed_windows == 1
    assert r.stats.dispatches == n_windows - 1
    assert len(eng._journals) == 0  # completion retires the journal
    np.testing.assert_array_equal(r.coadd, ref.coadd)
    np.testing.assert_array_equal(r.depth, ref.depth)


# ----- quarantine accounting -----------------------------------------------

def test_quarantine_completes_partial_with_correct_depth(survey):
    method = "sql_structured"
    packs, _ = _query_shape(survey, method)
    bad = int(packs[0])
    inj = ChaosInjector(FaultSchedule(
        poison=(PoisonSpec(pack=bad, mode="nan", count=None),)  # persistent
    ))
    r = _chaos(survey, injector=inj, on_fault="quarantine").run(QUERY, method)
    assert r.stats.partial
    assert r.stats.uncovered_packs == (bad,)
    assert r.stats.quarantined_packs == 1
    assert np.isfinite(r.coadd).all() and np.isfinite(r.depth).all()

    # Ground truth: the same query with the quarantined pack's slots gated
    # off at plan time (sql_structured plans on the execution layout, so
    # plan-gate packs == exec-gate packs).
    eng = _chaos(survey)
    plan = eng.plan(QUERY, method)
    plan.gate[bad] = False
    clean = eng.execute(plan)
    np.testing.assert_allclose(r.coadd, clean.coadd, rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(r.depth, clean.depth)
    assert r.stats.files_contributing == clean.stats.files_contributing


def test_persistent_poison_exhausts_retry_policy(survey):
    packs, _ = _query_shape(survey, "sql_structured")
    inj = ChaosInjector(FaultSchedule(
        poison=(PoisonSpec(pack=int(packs[0]), mode="nan", count=None),)
    ))
    with pytest.raises(PoisonedChunkError):
        _chaos(survey, injector=inj, on_fault="retry").run(QUERY, "sql_structured")


def test_raise_policy_aborts_on_first_fault(survey):
    inj = ChaosInjector(FaultSchedule(upload_fail_ordinals=(0,)))
    with pytest.raises(TransientFault):
        _chaos(survey, injector=inj, on_fault="raise").run(QUERY, "sql_structured")


def test_digest_verification_catches_finite_corruption(survey):
    """mode="flip" corruption is finite — invisible to the NaN scan, caught
    only by the per-pack digest comparison against the host seqfile."""
    method = "sql_structured"
    ref = _reference(survey, method)
    packs, _ = _query_shape(survey, method)
    spec = PoisonSpec(pack=int(packs[0]), mode="flip", count=1)
    # Without digests the corruption sails through (and corrupts the coadd).
    r_blind = _chaos(
        survey, injector=ChaosInjector(FaultSchedule(poison=(spec,)))
    ).run(QUERY, method)
    assert r_blind.stats.retries == 0
    # With digests it's detected, retried, and healed to bitwise parity.
    r = _chaos(
        survey, injector=ChaosInjector(FaultSchedule(poison=(spec,))),
        verify_digests=True,
    ).run(QUERY, method)
    assert r.stats.retries >= 1
    np.testing.assert_array_equal(r.coadd, ref.coadd)


# ----- batched streaming under faults --------------------------------------

def test_batch_streaming_heals_upload_failure(survey):
    q2 = CoaddQuery(band="g", ra_bounds=(37.2, 37.8), dec_bounds=(-0.5, 0.3),
                    npix=32)
    clean = _chaos(survey).run_batch([QUERY, q2], "sql_structured")
    inj = ChaosInjector(FaultSchedule(upload_fail_ordinals=(0,)))
    faulted = _chaos(survey, injector=inj).run_batch([QUERY, q2],
                                                     "sql_structured")
    assert faulted[0].stats.retries >= 1
    for c, f in zip(clean, faulted):
        np.testing.assert_array_equal(c.coadd, f.coadd)
        np.testing.assert_array_equal(c.depth, f.depth)


# ----- robust multi-pass fault domain (DESIGN.md §11) -----------------------

ROBUST = ("clipped", "median")
_ROBUST_REFS = {}


def _robust_reference(survey, red):
    """Fault-free streaming robust stack, shared across the robust matrix."""
    if red not in _ROBUST_REFS:
        _ROBUST_REFS[red] = _chaos(survey).run(QUERY, "sql_structured",
                                               reduce=red)
    return _ROBUST_REFS[red]


@pytest.mark.parametrize("red", ROBUST)
def test_robust_midpass_kill_replays_partial_journal_bitwise(survey, red):
    """A kill inside pass 1 leaves a partial pass-1 journal; the resume
    replays exactly the finished window and reproduces the uninterrupted
    robust stack bitwise."""
    ref = _robust_reference(survey, red)
    _, n_windows = _query_shape(survey, "sql_structured")
    assert n_windows >= 2
    inj = ChaosInjector(FaultSchedule(kill_after_windows=1))
    eng = _chaos(survey, injector=inj)
    with pytest.raises(QueryKilled):
        eng.run(QUERY, "sql_structured", reduce=red)
    assert len(eng._journals) == 1      # the killed pass's journal survives
    r = eng.run(QUERY, "sql_structured", reduce=red)
    assert r.stats.resumed_windows == 1  # only the finished window replays
    assert r.stats.reduce_passes == (3 if red == "median" else 2)
    assert len(eng._journals) == 0      # completion retires every pass journal
    np.testing.assert_array_equal(r.coadd, ref.coadd)
    np.testing.assert_array_equal(r.depth, ref.depth)


@pytest.mark.parametrize("red", ROBUST)
def test_robust_seam_kill_resumes_without_rerunning_pass1(survey, red):
    """A kill at the pass-1/pass-2 seam (every pass-1 window journaled,
    no later pass started) must resume by replaying ALL of pass 1 from the
    journal — zero re-executed pass-1 windows — and still match bitwise."""
    ref = _robust_reference(survey, red)
    _, n_windows = _query_shape(survey, "sql_structured")
    inj = ChaosInjector(FaultSchedule(kill_after_windows=n_windows))
    eng = _chaos(survey, injector=inj)
    with pytest.raises(QueryKilled):
        eng.run(QUERY, "sql_structured", reduce=red)
    assert len(eng._journals) == 1
    r = eng.run(QUERY, "sql_structured", reduce=red)
    assert r.stats.resumed_windows == n_windows  # pass 1 replayed, not rerun
    np.testing.assert_array_equal(r.coadd, ref.coadd)
    np.testing.assert_array_equal(r.depth, ref.depth)


@pytest.mark.parametrize("red", ROBUST)
def test_robust_upload_failure_retries_to_bitwise_parity(survey, red):
    ref = _robust_reference(survey, red)
    inj = ChaosInjector(FaultSchedule(upload_fail_ordinals=(0,)))
    r = _chaos(survey, injector=inj).run(QUERY, "sql_structured", reduce=red)
    assert inj.injected["upload_fail"] == 1
    assert r.stats.retries >= 1
    np.testing.assert_array_equal(r.coadd, ref.coadd)
    np.testing.assert_array_equal(r.depth, ref.depth)


@pytest.mark.parametrize("red", ROBUST)
def test_robust_quarantine_excludes_pack_from_every_pass(survey, red):
    """A persistently poisoned pack quarantined during pass 1 must stay
    excluded through the clip pass: the answer equals the clean robust run
    with that pack gated off at plan time (any pass disagreeing about the
    sample set would shift depth by whole coverage units)."""
    method = "sql_structured"
    packs, _ = _query_shape(survey, method)
    bad = int(packs[0])
    inj = ChaosInjector(FaultSchedule(
        poison=(PoisonSpec(pack=bad, mode="nan", count=None),)
    ))
    r = _chaos(survey, injector=inj, on_fault="quarantine").run(
        QUERY, method, reduce=red)
    assert r.stats.partial
    assert r.stats.uncovered_packs == (bad,)
    assert r.stats.quarantined_packs >= 1
    assert np.isfinite(r.coadd).all() and np.isfinite(r.depth).all()

    eng = _chaos(survey)
    plan = eng.plan(QUERY, method, reduce=red)
    plan.gate[bad] = False
    clean = eng.execute(plan)
    np.testing.assert_array_equal(r.depth, clean.depth)
    np.testing.assert_allclose(r.coadd, clean.coadd, rtol=1e-5, atol=1e-5)


# ----- the seeded acceptance drill -----------------------------------------

def test_seeded_chaos_drill_all_faults_at_once(survey):
    """The acceptance drill: a seeded schedule lands >=1 upload failure,
    >=1 poisoned chunk, and >=1 straggler in ONE 4x-oversubscribed query;
    retry+speculation reproduce the fault-free coadd bitwise."""
    method = "sql_structured"
    ref = _reference(survey, method, chunk_packs=1)
    packs, n_windows = _query_shape(survey, method, chunk_packs=1)
    sched = FaultSchedule.seeded(
        seed=82, n_uploads=n_windows, n_windows=n_windows, gated_packs=packs,
        upload_fails=1, poisons=1, stragglers=1, slow_s=0.05,
    )
    inj = ChaosInjector(sched)
    r = _chaos(survey, injector=inj, chunk_packs=1,
               straggler_factor=3.0).run(QUERY, method)
    assert inj.injected["upload_fail"] >= 1
    assert inj.injected["poison"] >= 1
    assert inj.injected["slow"] >= 1
    assert r.stats.retries >= 2  # the upload failure and the poison
    np.testing.assert_array_equal(r.coadd, ref.coadd)
    np.testing.assert_array_equal(r.depth, ref.depth)


def test_seeded_schedule_is_deterministic():
    packs = np.arange(12)
    a = FaultSchedule.seeded(seed=7, n_uploads=6, n_windows=6,
                             gated_packs=packs)
    b = FaultSchedule.seeded(seed=7, n_uploads=6, n_windows=6,
                             gated_packs=packs)
    assert a == b
    c = FaultSchedule.seeded(seed=8, n_uploads=6, n_windows=6,
                             gated_packs=packs)
    assert a != c


# ----- unit-level tracker/harness behavior ---------------------------------

class _FakeWin:
    def __init__(self, i):
        self.key = (i, i + 1, 1, 1)


def test_window_tracker_backoff_is_capped_exponential():
    sleeps = []
    tr = WindowTracker(backoff_s=0.1, backoff_cap_s=0.35, max_attempts=5,
                       sleep=sleeps.append)
    calls = [0]

    def acquire(win, quarantined):
        calls[0] += 1
        if calls[0] < 5:
            raise TransientFault("flaky")
        return "ops"

    out, quar = tr.run([_FakeWin(0)], acquire,
                       lambda ops, win, q: (np.ones(2),), {})
    assert sleeps == [0.1, 0.2, 0.35, 0.35]  # doubling, then capped
    assert tr.counters.retries == 4
    assert quar == []


def test_window_tracker_speculation_flags_nondeterminism():
    tr = WindowTracker(straggler_factor=1.5, straggler_min_windows=1)
    rng = np.random.default_rng(0)

    def dispatch(ops, win, quarantined):
        import time as _t
        if win.key[0] == 2:
            _t.sleep(0.05)  # the straggler: its backup re-rolls the dice
        return (rng.normal(size=4),)  # nondeterministic executor

    wins = [_FakeWin(i) for i in range(3)]
    with pytest.raises(DeterminismError):
        tr.run(wins, lambda w, q: "ops", dispatch, {})
    assert tr.counters.speculative_windows == 1


def test_window_tracker_fatal_errors_escape_immediately():
    tr = WindowTracker(max_attempts=5)
    attempts = [0]

    def acquire(win, quarantined):
        attempts[0] += 1
        raise ValueError("fatal config error")

    with pytest.raises(ValueError):
        tr.run([_FakeWin(0)], acquire, lambda o, w, q: (np.zeros(1),), {})
    assert attempts[0] == 1  # no retry net around fatal errors
    assert tr.counters.retries == 0


def test_classification_taxonomy():
    assert classify(TransientFault("x")) == "transient"
    assert classify(ConnectionError("x")) == "transient"
    assert classify(OSError("x")) == "transient"
    assert classify(RuntimeError("xla")) == "transient"  # injected faults
    assert classify(PoisonedChunkError([3])) == "transient"
    assert classify(DeterminismError("x")) == "fatal"
    assert classify(QueryKilled("x")) == "fatal"
    assert classify(ValueError("x")) == "fatal"
    assert classify(KeyError("x")) == "fatal"


def test_xla_compile_and_oom_errors_are_fatal():
    """A program the compiler refuses, or one that exhausts device memory,
    fails identically on every retry: no backoff loop, no quarantine."""
    import jax
    import jax.numpy as jnp

    with pytest.raises(jax.errors.JaxRuntimeError) as oom:
        jnp.zeros((1 << 40,), jnp.float32).block_until_ready()
    assert str(oom.value).startswith("RESOURCE_EXHAUSTED")
    assert classify(oom.value) == "fatal"
    compile_err = jax.errors.JaxRuntimeError(
        "INTERNAL: Mosaic failed to compile TPU kernel: unsupported shape cast"
    )
    assert classify(compile_err) == "fatal"
    assert classify(jax.errors.JaxRuntimeError("UNAVAILABLE: link down")) \
        == "transient"

    tr = WindowTracker(max_attempts=5)
    attempts = [0]

    def dispatch(ops, win, quarantined):
        attempts[0] += 1
        raise oom.value

    with pytest.raises(jax.errors.JaxRuntimeError):
        tr.run([_FakeWin(0)], lambda w, q: "ops", dispatch, {})
    assert attempts[0] == 1 and tr.counters.retries == 0


def test_partial_digest_distinguishes_content():
    a = (np.ones((4, 4)), np.zeros(3))
    b = (np.ones((4, 4)), np.zeros(3))
    c = (np.ones((4, 4)) * 2, np.zeros(3))
    assert partial_digest(a) == partial_digest(b)
    assert partial_digest(a) != partial_digest(c)


def test_residency_failed_build_leaves_manager_consistent():
    mgr = ResidencyManager(budget_bytes=100)
    mgr.acquire(("a",), 40, lambda: "A")
    with pytest.raises(TransientFault):
        mgr.acquire(("b",), 40, lambda: (_ for _ in ()).throw(
            TransientFault("upload lost")))
    assert mgr.failed_builds == 1
    assert mgr.n_resident == 1          # no phantom entry
    assert mgr.uploads == 1             # failed build never counted
    # Retry succeeds and the manager looks like the failure never happened.
    assert mgr.acquire(("b",), 40, lambda: "B") == "B"
    assert mgr.n_resident == 2 and mgr.uploads == 2


def test_residency_fault_hook_failure_counts_and_propagates():
    mgr = ResidencyManager(budget_bytes=100)
    fired = []

    def hook(key):
        fired.append(key)
        raise TransientFault("injected")

    mgr.fault_hook = hook
    with pytest.raises(TransientFault):
        mgr.acquire(("k",), 10, lambda: "payload")
    assert fired == [("k",)]
    assert mgr.failed_builds == 1 and mgr.n_resident == 0
    mgr.fault_hook = None
    assert mgr.acquire(("k",), 10, lambda: "payload") == "payload"


def test_scan_window_key_is_schedule_unique():
    wins = window_schedule(np.array([0, 1, 5, 9, 10, 11]), 12, 4)
    keys = [w.key for w in wins]
    assert len(set(keys)) == len(keys)
