"""Resilient training subsystem (dryad_tpu/resilience): fault
classification against the recorded fault signatures, deterministic
injection, ch_max threading/precedence, the supervised mixed-fault soak
(bitwise vs the uninterrupted run), and every fail-closed path."""

import os

import numpy as np
import pytest

import dryad_tpu as dryad
from dryad_tpu.datasets import higgs_like
from dryad_tpu.resilience import (
    FaultError,
    FaultInjector,
    FaultPoint,
    RetryPolicy,
    RunJournal,
    classify_fault,
    make_fault,
    supervise_train,
)
from dryad_tpu.resilience import faults as F
from dryad_tpu.resilience.policy import ChunkCapPolicy

PARAMS = dict(objective="binary", num_trees=16, num_leaves=7, max_bins=32,
              seed=3, min_data_in_leaf=5)


@pytest.fixture(scope="module")
def data():
    X, y = higgs_like(3000, seed=21)
    return dryad.Dataset(X, y, max_bins=32)


# ---- classification ---------------------------------------------------------

def test_classify_recorded_signatures():
    """The real messages from STATUS r5 map onto their classes; the
    UNAVAILABLE family splits on the fetch-site signal."""
    unavailable = RuntimeError(
        "UNAVAILABLE: TPU device error: worker process crashed")
    assert classify_fault(unavailable) == F.DEVICE_UNAVAILABLE
    assert classify_fault(unavailable, at_fetch=True) == F.FETCH_DEATH
    # a deadline-class message announces the fetch death itself
    assert classify_fault(RuntimeError("DEADLINE_EXCEEDED: ..."),
                          at_fetch=False) == F.FETCH_DEATH
    assert classify_fault(RuntimeError(
        "RESOURCE_EXHAUSTED: out of memory allocating 1.3G")) == F.OOM
    assert classify_fault(RuntimeError(
        "ABORTED: the TPU worker was preempted")) == F.PREEMPTION
    assert classify_fault(RuntimeError(
        "Preempted by the scheduler")) == F.PREEMPTION


def test_classify_fails_closed_on_everything_else():
    # user/config errors must NEVER be retried, whatever their message
    assert classify_fault(ValueError("UNAVAILABLE: looks like a device fault")) == F.UNKNOWN
    assert classify_fault(RuntimeError("some novel explosion")) == F.UNKNOWN
    assert classify_fault(KeyboardInterrupt()) == F.UNKNOWN
    # prose "aborted" is not the grpc ABORTED status — a deterministic bug
    # must not classify as a retryable preemption
    assert classify_fault(RuntimeError(
        "compilation aborted: invalid argument")) == F.UNKNOWN


def test_make_fault_roundtrips_through_classification():
    for kind in F.RETRYABLE:
        exc = make_fault(kind)
        assert isinstance(exc, RuntimeError)
        # the contract holds at ANY site: injected messages self-describe
        assert classify_fault(exc, at_fetch=False) == kind
        assert classify_fault(exc, at_fetch=True) in (kind, F.FETCH_DEATH)
    assert classify_fault(make_fault(F.UNKNOWN)) == F.UNKNOWN
    with pytest.raises(ValueError):
        make_fault("nope")


# ---- injector ---------------------------------------------------------------

def test_injector_fires_exactly_once_at_first_event_at_or_after():
    inj = FaultInjector([(5, F.OOM, "dispatch")])
    inj("fetch", 7)                    # wrong site: no fire
    inj("dispatch", 3)                 # too early: no fire
    with pytest.raises(RuntimeError):
        inj("dispatch", 6)             # first dispatch >= 5
    inj("dispatch", 6)                 # spent: silent on replay
    assert inj.pending == 0
    assert inj.fired == [{"point": 0, "site": "dispatch", "iteration": 6,
                          "kind": F.OOM}]
    with pytest.raises(ValueError):
        FaultPoint(0, site="telepathy")


# ---- ch_max threading (satellite) ------------------------------------------

def test_ch_max_param_caps_chunks_and_lands_in_info(data, monkeypatch):
    monkeypatch.delenv("DRYAD_CH_MAX", raising=False)
    seen, infos = [], []
    dryad.train(dict(PARAMS, ch_max=3), data, backend="tpu",
                chunk_hook=lambda s, it: seen.append(it) if s == "dispatch"
                else None,
                callback=lambda it, info: infos.append(info))
    assert seen == [0, 3, 6, 9, 12, 15]
    assert infos and all(i["ch_max_effective"] == 3 for i in infos)


def test_ch_max_env_overrides_param(data, monkeypatch):
    """Documented precedence: DRYAD_CH_MAX, when set, beats Params.ch_max."""
    monkeypatch.setenv("DRYAD_CH_MAX", "2")
    seen, infos = [], []
    b = dryad.train(dict(PARAMS, ch_max=5), data, backend="tpu",
                    chunk_hook=lambda s, it: seen.append(it)
                    if s == "dispatch" else None,
                    callback=lambda it, info: infos.append(info))
    assert seen == list(range(0, 16, 2))
    assert all(i["ch_max_effective"] == 2 for i in infos)
    assert b.train_state["ch_max_effective"] == 2


def test_ch_max_key_present_on_per_iteration_path(data):
    """The documented info/train_state key exists on EVERY path — the
    per-iteration dispatch (DART pins it) reports 0: no chunks, no cap."""
    infos = []
    b = dryad.train(dict(PARAMS, boosting="dart", num_trees=4), data,
                    backend="tpu", callback=lambda it, i: infos.append(i))
    assert infos and all(i["ch_max_effective"] == 0 for i in infos)
    assert b.train_state["ch_max_effective"] == 0


def test_ch_max_does_not_change_the_model(data, monkeypatch):
    """Chunk length is a traced scalar of one shared program — capping it
    must be invisible in the trees (the property the supervisor's
    degradation lever rests on)."""
    monkeypatch.delenv("DRYAD_CH_MAX", raising=False)
    a = dryad.train(PARAMS, data, backend="tpu")
    b = dryad.train(dict(PARAMS, ch_max=2), data, backend="tpu")
    np.testing.assert_array_equal(a.feature, b.feature)
    np.testing.assert_array_equal(a.threshold, b.threshold)
    np.testing.assert_array_equal(a.value, b.value)


# ---- chunk-cap policy -------------------------------------------------------

def test_chunk_cap_ladder_degrade_and_rewiden():
    cap = ChunkCapPolicy(RetryPolicy(rewiden_after_clean_chunks=2))
    assert cap.cap() == 0
    # first degrade with NO length observed: ladder top, nothing fatal yet
    assert cap.degrade() == 8
    cap.note_clean_chunk()
    assert cap.cap() == 8                  # not yet
    cap.note_clean_chunk()
    assert cap.cap() == 0                  # no fatal on record: uncapped again
    # full walk-down: each further degrade means the CURRENT length faulted,
    # so every visited length lands on the fatal record
    assert cap.degrade() == 8 and cap.degrade() == 4 and cap.degrade() == 2
    assert cap.degrade() == 2              # floor holds
    for _ in range(4):
        cap.note_clean_chunk()
    assert cap.cap() == 2                  # 4 and 8 both faulted: hold at floor
    # a start below the ladder floor must never be WIDENED by degrade()
    tight = ChunkCapPolicy(RetryPolicy(ch_max_start=1))
    assert tight.degrade() == 1
    # degrade targets a step STRICTLY below the observed chunk length —
    # a ladder top at/above the calibrated CH would replay the fatal length.
    # The length is known from DISPATCH (the r5 first-fetch-death mode:
    # the fatal chunk never completed cleanly)
    seen = ChunkCapPolicy(RetryPolicy())
    seen.note_dispatch(6)                  # calibrated CH ~6 was dispatched
    assert seen.degrade() == 4
    # a cap ABOVE the calibrated CH never governed what ran: the observed
    # length is the reference the first step must undercut
    wide = ChunkCapPolicy(RetryPolicy(ch_max_start=8))
    wide.note_dispatch(3)                  # chunks really ran at 3
    assert wide.degrade() == 2 and wide.last_shrunk
    # fatal length already at/below the floor: cap lands on the floor but
    # the journal must read "remedy exhausted", not "applied"
    exhausted = ChunkCapPolicy(RetryPolicy())
    exhausted.note_dispatch(2)
    assert exhausted.degrade() == 2 and not exhausted.last_shrunk
    # an ascending user ladder is normalized widest-first, not inverted
    asc = ChunkCapPolicy(RetryPolicy(ch_max_ladder=(2, 4, 8)))
    assert asc.degrade() == 8
    with pytest.raises(ValueError, match="at least one step"):
        ChunkCapPolicy(RetryPolicy(ch_max_ladder=()))
    # re-widening never returns to a known-fatal length: a persistent
    # faulty phase must not oscillate safe -> fatal -> safe and burn the
    # retry budget (the recorded r5 mode: 6-8 fatal, <= 2 always clean)
    osc = ChunkCapPolicy(RetryPolicy(rewiden_after_clean_chunks=1))
    osc.note_dispatch(6)
    assert osc.degrade() == 4              # fatal length 6 on record
    assert osc.degrade() == 2              # faulted again at 4 -> fatal 4
    osc.note_clean_chunk()
    assert osc.cap() == 2                  # no ladder step in (2, 4): hold
    # cadence tightening is monotone non-increasing with a floor well
    # above per-iteration checkpointing (a materialize fetch per iteration
    # is the fetch-bound pattern the cadence exists to avoid)
    pol = RetryPolicy()
    assert pol.next_checkpoint_every(50) == 25
    assert pol.next_checkpoint_every(6) == 5
    assert pol.next_checkpoint_every(2) == 2   # never loosened to the floor


# ---- the supervised soak (acceptance criterion) -----------------------------

def test_supervised_soak_mixed_faults_bitwise(data, tmp_path):
    """>= 3 injected faults of mixed classes — including a fetch-death that
    degrades the chunk cap to 2 — complete bitwise-identical to the
    uninterrupted run, with the journal recording every classification,
    backoff, and resume."""
    reference = dryad.train(PARAMS, data, backend="tpu")
    injector = FaultInjector([
        (3, F.DEVICE_UNAVAILABLE, "dispatch"),
        (6, F.OOM, "dispatch"),
        (10, F.FETCH_DEATH, "fetch"),
    ])
    jpath = str(tmp_path / "journal.jsonl")
    infos = []
    booster = supervise_train(
        PARAMS, data, backend="tpu",
        checkpoint_dir=str(tmp_path / "ck"), checkpoint_every=2,
        journal=jpath, fault_injector=injector,
        callback=lambda it, info: infos.append(info),
        policy=RetryPolicy(backoff_base_s=0.0, ch_max_ladder=(2,)))

    assert injector.pending == 0
    np.testing.assert_array_equal(reference.feature, booster.feature)
    np.testing.assert_array_equal(reference.threshold, booster.threshold)
    np.testing.assert_array_equal(reference.value, booster.value)
    Xp = np.zeros((4, data.num_features), np.float32)
    np.testing.assert_array_equal(reference.predict(Xp), booster.predict(Xp))

    events = RunJournal.read(jpath)
    kinds = [e["event"] for e in events]
    assert kinds[0] == "run_start" and kinds[-1] == "complete"
    faults = [e for e in events if e["event"] == "fault"]
    assert [f["kind"] for f in faults] == [
        F.DEVICE_UNAVAILABLE, F.OOM, F.FETCH_DEATH]
    # exactly-once resume per fault, and resume points advance (the
    # same-point breaker never engaged)
    assert kinds.count("resume") == 3 and kinds.count("segment_start") == 4
    resume_points = [e["from_iteration"] for e in events
                     if e["event"] == "resume"]
    assert resume_points == sorted(resume_points)
    backoff = [e for e in events if e["event"] == "backoff_chunks"]
    assert len(backoff) == 1 and backoff[0]["ch_max_to"] == 2
    # the faulted segment ran the chunked path, so the cap was really in
    # force there — "remedy applied", not "remedy inapplicable"
    assert backoff[0]["cap_consulted"] is True
    # replayed iterations (checkpoint..fault span, re-grown bitwise) carry
    # the attempt marker so consumers can dedupe: keep the highest attempt
    assert all("supervise_attempt" in i for i in infos)
    assert {i["supervise_attempt"] for i in infos} == {0, 1, 2, 3}
    its_seen = [i["iteration"] for i in infos]
    assert len(its_seen) > len(set(its_seen)), "no replayed iterations?"
    # degraded segments record the live cap in the callback info dicts via
    # the chunk events; the journal carries dispatch/fetch traffic too
    assert any(e["event"] == "chunk_dispatch" for e in events)
    assert any(e["event"] == "chunk_fetch" for e in events)
    assert events[-1]["faults"] == 3


def test_supervised_warm_start_resumes_from_checkpoint(data, tmp_path):
    """A caller-supplied init_booster seeds only the checkpoint-less first
    segment — post-fault retries must continue from the newest checkpoint
    (which embodies warm start + progress), not redo the faulted segment
    from the warm booster."""
    warm = dryad.train(dict(PARAMS, num_trees=4), data, backend="tpu")
    full = dryad.train(PARAMS, data, backend="tpu", init_booster=warm)
    injector = FaultInjector([(8, F.DEVICE_UNAVAILABLE, "dispatch")])
    jpath = str(tmp_path / "j.jsonl")
    resumed = supervise_train(
        PARAMS, data, backend="tpu", init_booster=warm,
        checkpoint_dir=str(tmp_path / "ck"), checkpoint_every=2,
        journal=jpath, fault_injector=injector,
        policy=RetryPolicy(backoff_base_s=0.0))
    assert injector.pending == 0
    np.testing.assert_array_equal(full.feature, resumed.feature)
    np.testing.assert_array_equal(full.value, resumed.value)
    resumes = [e for e in RunJournal.read(jpath) if e["event"] == "resume"]
    # the retry really continued past the warm start instead of redoing it
    assert resumes and resumes[0]["from_iteration"] > warm.num_iterations


def test_supervised_cpu_backend_bitwise(data, tmp_path):
    """The same supervision loop covers the CPU reference trainer (its
    per-iteration loop exposes the same hook sites)."""
    reference = dryad.train(PARAMS, data, backend="cpu")
    injector = FaultInjector([(5, F.DEVICE_UNAVAILABLE, "dispatch"),
                              (9, F.OOM, "fetch")])
    infos = []
    booster = supervise_train(
        PARAMS, data, backend="cpu",
        checkpoint_dir=str(tmp_path / "ck"), checkpoint_every=3,
        callback=lambda it, i: infos.append(i),
        fault_injector=injector, policy=RetryPolicy(backoff_base_s=0.0))
    assert injector.pending == 0
    # the documented info-dict contract holds on the CPU backend too
    assert infos and all(i["ch_max_effective"] == 0 for i in infos)
    np.testing.assert_array_equal(reference.feature, booster.feature)
    np.testing.assert_array_equal(reference.value, booster.value)


# ---- fail-closed paths ------------------------------------------------------

def test_unknown_fault_fails_closed(data, tmp_path):
    injector = FaultInjector([(2, F.UNKNOWN, "dispatch")])
    jpath = str(tmp_path / "j.jsonl")
    with pytest.raises(FaultError) as ei:
        supervise_train(PARAMS, data, backend="tpu",
                        checkpoint_dir=str(tmp_path / "ck"),
                        checkpoint_every=2, journal=jpath,
                        fault_injector=injector,
                        policy=RetryPolicy(backoff_base_s=0.0))
    assert ei.value.reason == "unknown_fault"
    assert ei.value.__cause__ is not None        # original exception chained
    events = RunJournal.read(jpath)
    kinds = [e["event"] for e in events]
    assert kinds.count("segment_start") == 1     # no retry happened
    assert kinds[-1] == "fail_closed"
    assert events[-1]["reason"] == "unknown_fault"


def test_retry_budget_exhausted_fails_closed(data, tmp_path):
    injector = FaultInjector([(2, F.DEVICE_UNAVAILABLE, "dispatch"),
                              (8, F.DEVICE_UNAVAILABLE, "dispatch")])
    jpath = str(tmp_path / "j.jsonl")
    with pytest.raises(FaultError) as ei:
        supervise_train(PARAMS, data, backend="tpu",
                        checkpoint_dir=str(tmp_path / "ck"),
                        checkpoint_every=2, journal=jpath,
                        fault_injector=injector,
                        policy=RetryPolicy(retry_budget=1,
                                           backoff_base_s=0.0))
    assert ei.value.reason == "retry_budget_exhausted"
    events = RunJournal.read(jpath)
    assert events[-1]["reason"] == "retry_budget_exhausted"
    assert [e["event"] for e in events].count("resume") == 1  # first fault only


def test_repeated_same_point_fails_closed(data, tmp_path):
    """Faults with NO checkpoint progress in between (cadence too wide for
    any checkpoint to land) trip the same-point breaker."""
    injector = FaultInjector([(0, F.DEVICE_UNAVAILABLE, "dispatch"),
                              (0, F.DEVICE_UNAVAILABLE, "dispatch"),
                              (0, F.DEVICE_UNAVAILABLE, "dispatch")])
    with pytest.raises(FaultError) as ei:
        supervise_train(PARAMS, data, backend="tpu",
                        checkpoint_dir=str(tmp_path / "ck"),
                        checkpoint_every=100,
                        fault_injector=injector,
                        policy=RetryPolicy(backoff_base_s=0.0,
                                           same_point_retries=2))
    assert ei.value.reason == "repeated_fault_at_same_iteration"


def test_same_point_device_unavailable_degrades_as_fallback(data, tmp_path):
    """A killed fetch can surface at the NEXT enqueue (a dispatch site),
    classifying as device_unavailable — on a no-progress repeat the chunk
    remedy must still be tried before the same-point breaker fires."""
    injector = FaultInjector([(0, F.DEVICE_UNAVAILABLE, "dispatch"),
                              (0, F.DEVICE_UNAVAILABLE, "dispatch")])
    jpath = str(tmp_path / "j.jsonl")
    booster = supervise_train(
        PARAMS, data, backend="tpu",
        checkpoint_dir=str(tmp_path / "ck"), checkpoint_every=100,
        journal=jpath, fault_injector=injector,
        policy=RetryPolicy(backoff_base_s=0.0))
    assert injector.pending == 0
    assert booster.num_iterations == PARAMS["num_trees"]
    events = RunJournal.read(jpath)
    backoffs = [e for e in events if e["event"] == "backoff_chunks"]
    # first fault: plain resume; the same-point repeat engages the remedy
    assert len(backoffs) == 1
    assert backoffs[0]["trigger"] == "same_point_device_unavailable"


def test_supervise_requires_checkpoint_dir(data):
    with pytest.raises(ValueError, match="checkpoint_dir"):
        supervise_train(PARAMS, data, backend="cpu")


def test_supervise_owns_resume_kwarg(data, tmp_path):
    """A caller's resume= must not collide with the supervisor's own
    resume=True (dryad.train would raise an opaque TypeError), and the
    composed hook surfaces are rejected up front with a clear error."""
    b = supervise_train(PARAMS, data, backend="cpu", resume=True,
                        checkpoint_dir=str(tmp_path / "ck"))
    assert b.num_iterations == PARAMS["num_trees"]
    # an explicit resume=False is contradictory, not silently swallowed
    with pytest.raises(ValueError, match="resume=False is contradictory"):
        supervise_train(PARAMS, data, backend="cpu", resume=False,
                        checkpoint_dir=str(tmp_path / "ck3"))
    with pytest.raises(ValueError, match="composes its own chunk_hook"):
        supervise_train(PARAMS, data, backend="cpu",
                        checkpoint_dir=str(tmp_path / "ck2"),
                        chunk_hook=lambda s, i: None)


def test_journal_closed_on_error_outside_classified_path(data, tmp_path):
    """An exception raised OUTSIDE the classified try (bad cadence) still
    closes an owned journal."""
    jpath = str(tmp_path / "j.jsonl")
    with pytest.raises(ValueError):
        supervise_train(PARAMS, data, backend="cpu", journal=jpath,
                        checkpoint_dir=str(tmp_path / "ck"),
                        checkpoint_every=0)
    events = RunJournal.read(jpath)          # parseable: handle was closed
    assert events and events[0]["event"] == "run_start"


def test_mesh_with_cpu_backend_rejected(data):
    import jax

    from dryad_tpu.engine.distributed import make_mesh

    with pytest.raises(ValueError, match="mesh requires"):
        dryad.train(PARAMS, data, backend="cpu",
                    mesh=make_mesh(jax.devices()[:2]))


# ---- journal ----------------------------------------------------------------

def test_journal_shape_and_ownership(data, tmp_path):
    jpath = str(tmp_path / "j.jsonl")
    supervise_train(PARAMS, data, backend="cpu",
                    checkpoint_dir=str(tmp_path / "ck"), journal=jpath)
    events = RunJournal.read(jpath)
    assert events[0]["event"] == "run_start"
    assert events[-1]["event"] == "complete"
    assert all("elapsed_s" in e for e in events)
    assert events[-1]["iterations"] == PARAMS["num_trees"]
    assert events[-1]["faults"] == 0
    # fault-free supervision leaves no fault/backoff/resume records
    assert not any(e["event"] in ("fault", "resume", "backoff_chunks",
                                  "fail_closed") for e in events)
    assert os.path.getsize(jpath) > 0
