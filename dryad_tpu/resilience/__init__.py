"""dryad_tpu.resilience — supervised training for long runs.

The subsystem that makes the recorded device fault classes
survivable without a human: fault classification + deterministic
injection (faults.py), retry/degradation policy (policy.py), the
supervising driver (supervisor.py), and the append-only run journal
(journal.py).  Entry point::

    from dryad_tpu.resilience import supervise_train
    booster = supervise_train(params, ds, [vds], checkpoint_dir="ck/",
                              checkpoint_every=50, journal="run.jsonl")

or ``python -m dryad_tpu train ... --supervise --journal run.jsonl``.
"""

from dryad_tpu.resilience.faults import (
    DEVICE_UNAVAILABLE,
    FETCH_DEATH,
    OOM,
    PREEMPTION,
    REJECT_503,
    REPLICA_CRASH,
    REPLICA_CRASH_EXIT,
    REPLICA_KINDS,
    RETRYABLE,
    SLOW_HEALTH,
    UNKNOWN,
    FaultInjector,
    FaultPoint,
    InjectedReject,
    classify_fault,
    make_fault,
)
from dryad_tpu.resilience.journal import RunJournal
from dryad_tpu.resilience.policy import ChunkCapPolicy, RetryPolicy
from dryad_tpu.resilience.supervisor import FaultError, supervise_train

__all__ = [
    "DEVICE_UNAVAILABLE", "FETCH_DEATH", "OOM", "PREEMPTION", "RETRYABLE",
    "REJECT_503", "REPLICA_CRASH", "REPLICA_CRASH_EXIT", "REPLICA_KINDS",
    "SLOW_HEALTH", "UNKNOWN", "FaultInjector", "FaultPoint", "InjectedReject",
    "classify_fault", "make_fault",
    "RunJournal", "ChunkCapPolicy", "RetryPolicy", "FaultError",
    "supervise_train",
]
