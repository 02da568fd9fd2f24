"""Supervised training: survive the recorded device fault classes
without a human in the loop.

``supervise_train`` wraps ``dryad.train`` in a classify → degrade →
resume → retry loop.  The expensive invariant it exploits already exists
and is test-pinned (checkpoint → crash → resume is bitwise identical to
the uninterrupted run — tests/test_checkpoint.py, the mocked multi-host
drill); this module is the subsystem that exercises it automatically:

1. a raised failure is classified against the REAL recorded fault
   signatures (faults.py; STATUS r5) — unknown classes FAIL CLOSED,
2. fetch-death-class faults degrade the chunk cap stepwise toward the
   known-safe 2 (policy.ChunkCapPolicy; applied per chunk AFTER program
   selection, so degradation can never flip the compiled program),
3. the checkpoint cadence tightens after each fault (less replay at the
   next one),
4. training resumes from ``Checkpointer.latest()`` under an exponential
   backoff and a hard retry budget; repeated faults with NO checkpoint
   progress in between fail closed after ``policy.same_point_retries``.

Every classification, backoff, degradation, and resume decision lands in
the append-only run journal (journal.py).

Supervised output is bitwise identical to the uninterrupted run: resume
identity is the pinned invariant, and both of the supervisor's levers
(chunk length, checkpoint cadence) are host-side scheduling knobs of one
shared compiled program.
"""

from __future__ import annotations

import os
import time
from typing import Any, Optional

from dryad_tpu.checkpoint import Checkpointer
from dryad_tpu.obs.spans import record as record_span
from dryad_tpu.obs.spans import span
from dryad_tpu.obs.tripwire import default_tripwire
from dryad_tpu.obs.watchdog import default_watchdog
from dryad_tpu.resilience import faults as F
from dryad_tpu.resilience.journal import RunJournal
from dryad_tpu.resilience.policy import ChunkCapPolicy, RetryPolicy


class FaultError(RuntimeError):
    """Fail-closed terminus: the supervisor refuses to keep retrying.
    ``kind`` is the last fault's class, ``reason`` why retrying stopped
    (``unknown_fault`` / ``retry_budget_exhausted`` /
    ``repeated_fault_at_same_iteration``); the original exception is
    chained as ``__cause__``."""

    def __init__(self, message: str, kind: str, reason: str):
        super().__init__(message)
        self.kind = kind
        self.reason = reason


def supervise_train(
    params,
    train_set,
    valid_sets=None,
    *,
    policy: Optional[RetryPolicy] = None,
    backend: str = "auto",
    checkpoint_dir: Optional[str] = None,
    checkpoint_every: int = 10,
    journal: "RunJournal | str | None" = None,
    fault_injector=None,
    callbacks=None,
    callback=None,
    valid_names=None,
    mesh=None,
    **kw: Any,
):
    """Train under supervision; returns the finished Booster.

    ``checkpoint_dir`` is REQUIRED — resume is the recovery mechanism.
    NOTE the directory is continued unconditionally: checkpoints already
    present (a prior invocation's) resume exactly like a mid-run fault's.
    Callers owning a user surface should confirm cross-invocation
    continuation explicitly (the CLI requires ``--resume`` for it).
    ``journal`` takes a path (owned/closed here) or an open ``RunJournal``.
    ``fault_injector`` threads a deterministic ``faults.FaultInjector``
    into the trainer's chunk loop (CPU-testable resilience paths); extra
    ``**kw`` forward to ``dryad.train`` (and through it to params).
    """
    import dryad_tpu as dryad

    policy = policy or RetryPolicy()
    if checkpoint_dir is None:
        raise ValueError("supervise_train requires checkpoint_dir: resume "
                         "from the latest checkpoint is the recovery path")

    # a caller's warm-start booster seeds ONLY the first, checkpoint-less
    # segment: once any checkpoint exists it embodies warm start + progress,
    # and passing init_booster through would make dryad.train's
    # "resume only when init_booster is None" guard skip the checkpoint —
    # every retry would silently redo the whole faulted segment
    init_booster = kw.pop("init_booster", None)
    # init_model (r19): the public APPEND surface — num_trees counts NEW
    # trees.  Normalize it ONCE into the total-count init_booster form so
    # every resumed segment sees one consistent target; the append count
    # must live in ``params`` here (not a loose kwarg), because the
    # conversion happens before dryad.train's params merge.
    init_model = kw.pop("init_model", None)
    if init_model is not None:
        if init_booster is not None:
            raise ValueError("pass init_model (append semantics) or "
                             "init_booster (total-count resume), not both")
        from dryad_tpu.config import make_params
        p0 = make_params(params)
        dryad._check_append_compatible(p0, train_set, init_model)
        params = p0.replace(num_trees=p0.num_trees
                            + init_model.num_iterations)
        init_booster = init_model
    # the supervisor OWNS resume semantics (every segment passes
    # resume=True); a caller's resume= kwarg would otherwise collide in
    # dryad.train with an opaque TypeError.  An explicit resume=False is
    # contradictory — silently swallowing it would continue a stale
    # directory the caller just said NOT to continue.
    if kw.pop("resume", True) is False:
        raise ValueError(
            "supervise_train always resumes from checkpoint_dir (that IS "
            "the recovery mechanism); resume=False is contradictory — "
            "point checkpoint_dir at a fresh or cleared directory to "
            "start over")
    # likewise it owns the loop-observation surfaces it composes — reject
    # them up front instead of letting **kw collide deep inside a segment
    for owned in ("chunk_hook", "chunk_policy"):
        if owned in kw:
            raise ValueError(
                f"supervise_train composes its own {owned} (journal + "
                "injection + adaptive cap); pass fault_injector/journal "
                "here, or call dryad.train directly for raw hook access")

    own_journal = isinstance(journal, (str, os.PathLike))
    j = RunJournal(os.fspath(journal)) if own_journal else journal

    def jevent(kind: str, /, **fields) -> None:
        if j is not None:
            j.event(kind, **fields)

    # the trainers' chunk_hook: record loop events + track the last site so
    # a raised UNAVAILABLE can be attributed to a fetch (faults.py), then
    # give the injector its shot
    last = {"site": None, "iteration": -1}

    def hook(site: str, iteration: int) -> None:
        last["site"], last["iteration"] = site, int(iteration)
        jevent("chunk_" + site, iteration=int(iteration))
        if fault_injector is not None:
            fault_injector(site, iteration)

    # replay visibility: a resumed segment re-delivers callbacks for the
    # iterations re-grown since the checkpoint (values bitwise-identical to
    # the first delivery).  The attempt marker lets consumers dedupe —
    # keep the highest supervise_attempt per iteration.
    from dryad_tpu.callbacks import combine

    user_cb = combine(([callback] if callback else []) + list(callbacks or []))
    marked_cb = None
    if user_cb is not None:
        def marked_cb(it, info):
            info = dict(info)
            info["supervise_attempt"] = n_faults
            user_cb(it, info)

    chunk_cap = ChunkCapPolicy(policy)
    every = int(checkpoint_every)
    n_faults = 0
    same_point = 0
    last_resume_iter: Optional[int] = None
    t0 = time.perf_counter()

    def latest_iteration() -> int:
        # iterations() is a directory listing — never deserialize a
        # (potentially multi-hundred-MB) checkpoint just to read its number
        its = Checkpointer(checkpoint_dir, every=every).iterations()
        return its[-1] if its else 0

    jevent("run_start", checkpoint_dir=checkpoint_dir,
           checkpoint_every=every, backend=backend,
           retry_budget=policy.retry_budget)

    # r12: unexpected recompiles (obs/tripwire.py — a new program key
    # after the trainer armed its family) land in the journal as events,
    # so the flight recorder correlates them with the faults that follow
    _remove_tw = default_tripwire().add_listener(
        lambda program, detail: jevent("recompile_unexpected",
                                       program=program, detail=detail))

    def _loop():
        nonlocal n_faults, same_point, last_resume_iter, every
        while True:
            resume_iter = latest_iteration()
            # fresh site tracking per segment: a fault raised before this
            # segment's first hook (device re-init, compile, upload) must
            # not inherit the PREVIOUS segment's fetch attribution;
            # likewise the cap-consulted flag is per segment
            last["site"], last["iteration"] = None, -1
            chunk_cap.consulted = False
            jevent("segment_start", attempt=n_faults,
                   resume_iteration=resume_iter, ch_max=chunk_cap.peek(),
                   checkpoint_every=every)
            # segment wall via record(), NOT a with-span: a with-block here
            # would prefix every nested with-span the trainer emits
            # (train.fetch.* -> supervise.segment/train.fetch.*), splitting
            # the train series across supervised/unsupervised naming
            _t_seg = time.perf_counter()
            try:
                booster = dryad.train(
                    params, train_set, valid_sets,
                    valid_names=valid_names,
                    backend=backend, checkpoint_dir=checkpoint_dir,
                    checkpoint_every=every, resume=True,
                    # resume_iter > 0 iff a checkpoint exists (they
                    # number from 1): the checkpoint then embodies the
                    # warm start, which must not shadow it
                    init_booster=init_booster if resume_iter == 0 else None,
                    callback=marked_cb, mesh=mesh,
                    chunk_hook=hook, chunk_policy=chunk_cap, **kw)
                record_span("supervise.segment",
                            time.perf_counter() - _t_seg)
            except Exception as exc:  # noqa: BLE001 — classified just below
                record_span("supervise.segment",
                            time.perf_counter() - _t_seg)
                _t_cl = time.perf_counter()
                kind = F.classify_fault(exc, at_fetch=last["site"] == "fetch")
                record_span("supervise.classify",
                            time.perf_counter() - _t_cl)
                ckpt_iter = latest_iteration()
                # stall correlation (r12): if the fetch watchdog saw a
                # stall during THIS segment, record its age next to the
                # classification — the journal then shows "pending 43 s,
                # then fetch_death" instead of a death from nowhere
                stall = default_watchdog().last_stall()
                extra = {}
                if stall is not None and stall.get("ended_at", 0) >= _t_seg:
                    extra = {"stall_age_s": stall["age_s"],
                             "stall_site": stall["site"]}
                jevent("fault", kind=kind, site=last["site"],
                       iteration=last["iteration"], resume_point=ckpt_iter,
                       message=str(exc)[:300], **extra)
                if kind == F.UNKNOWN:
                    jevent("fail_closed", reason="unknown_fault",
                           message=str(exc)[:300])
                    raise FaultError(
                        f"unclassified failure — refusing to retry: {exc}",
                        kind, "unknown_fault") from exc
                n_faults += 1
                if n_faults > policy.retry_budget:
                    jevent("fail_closed", reason="retry_budget_exhausted",
                           faults=n_faults)
                    raise FaultError(
                        f"retry budget ({policy.retry_budget}) exhausted "
                        f"after a {kind} fault: {exc}", kind,
                        "retry_budget_exhausted") from exc
                if (last_resume_iter is not None
                        and ckpt_iter == last_resume_iter):
                    same_point += 1
                    if same_point >= policy.same_point_retries:
                        jevent("fail_closed",
                               reason="repeated_fault_at_same_iteration",
                               resume_point=ckpt_iter, repeats=same_point)
                        raise FaultError(
                            f"{kind} fault repeated {same_point}x with no "
                            f"checkpoint progress past iteration {ckpt_iter}",
                            kind, "repeated_fault_at_same_iteration") from exc
                else:
                    same_point = 0
                last_resume_iter = ckpt_iter
                # the recorded remedy — shorter chunks (STATUS r5) — engages
                # on a classified fetch-death, AND as a fallback on a
                # device_unavailable that REPEATS with no checkpoint
                # progress: with async dispatch a killed fetch can surface
                # at the next enqueue (a dispatch site), where site
                # attribution cannot see it — the remedy must still be
                # tried before the same-point breaker fails the run closed.
                degrade_now = kind == F.FETCH_DEATH or (
                    kind == F.DEVICE_UNAVAILABLE and same_point >= 1)
                if degrade_now:
                    # cap_consulted says whether the faulted segment's
                    # trainer ever READ the cap — False means a non-chunked
                    # dispatch path, where degradation is a no-op the
                    # operator should see as "inapplicable", not "tried
                    # and failed".
                    # changed=False tells the operator the remedy was
                    # already exhausted (cap at/below the ladder floor),
                    # not meaningfully re-applied
                    before = chunk_cap.peek()
                    consulted = chunk_cap.consulted
                    after = chunk_cap.degrade()
                    jevent("backoff_chunks", ch_max_from=before,
                           ch_max_to=after, cap_consulted=consulted,
                           changed=chunk_cap.last_shrunk,
                           trigger=("fetch_death" if kind == F.FETCH_DEATH
                                    else "same_point_device_unavailable"))
                new_every = policy.next_checkpoint_every(every)
                sleep_s = policy.backoff_s(n_faults - 1)
                jevent("resume", attempt=n_faults, from_iteration=ckpt_iter,
                       sleep_s=sleep_s, checkpoint_every=new_every)
                every = new_every
                if sleep_s > 0:
                    with span("supervise.backoff"):
                        time.sleep(sleep_s)
                continue
            wall = time.perf_counter() - t0
            jevent("complete", wall_s=round(wall, 3),
                   iterations=booster.num_iterations, faults=n_faults,
                   ch_max_final=chunk_cap.peek())
            return booster

    try:
        return _loop()
    finally:
        # EVERY exit — completion, fail-closed, an unexpected error raised
        # outside the classified path, Ctrl-C mid-backoff — releases an
        # owned journal handle (and the tripwire listener, which holds it)
        _remove_tw()
        _close(j, own_journal)


def _close(j: Optional[RunJournal], owned: bool) -> None:
    if owned and j is not None:
        j.close()
