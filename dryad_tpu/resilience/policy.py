"""Retry + degradation policy for supervised runs.

Two cooperating pieces:

* ``RetryPolicy`` — the static knobs: retry budget, exponential backoff,
  the chunk-cap degradation ladder, checkpoint-cadence tightening.
* ``ChunkCapPolicy`` — the LIVE chunk-cap controller the supervisor
  threads into ``train_device(chunk_policy=...)``.  The trainer consults
  ``cap()`` per chunk (after path selection and calibration, so a cap
  change can never flip the compiled program — engine/train.py) and calls
  ``note_clean_chunk()`` after each chunk's host work completes, which
  drives the re-widening side of the ladder.

Degradation walks ``ch_max_ladder`` stepwise toward its floor (recorded
in round 5: ``DRYAD_CH_MAX=2`` survived every phase that killed standard
~20 s chunks); re-widening walks back up one step after
``rewiden_after_clean_chunks`` consecutive clean chunks, eventually
returning to uncapped.  Because the trainer's run-ahead cap keeps device
completion within 2 chunks of the host, a "clean chunk" signal is at most
two chunks optimistic — the ladder step (not the counter's exactness) is
what bounds risk.
"""

from __future__ import annotations

import dataclasses


def _default_ladder() -> tuple[int, ...]:
    """The degradation ladder (policy/gates.py "chunk_cap"/"ladder":
    ``(8, 4, 2)``, ending on the 2-iteration floor).  The policy
    package is stdlib-only, so this keeps the module jax-free."""
    from dryad_tpu.policy.gates import gate_value

    return tuple(int(s) for s in gate_value("chunk_cap", "ladder"))


@dataclasses.dataclass(frozen=True)
class RetryPolicy:
    """Static supervision knobs (see module docstring)."""

    #: total classified faults tolerated before failing closed
    retry_budget: int = 5
    #: faults tolerated at ONE resume point (no checkpoint progress in
    #: between) before failing closed — covers one full walk down the
    #: default chunk ladder, since degradation is the legitimate reason a
    #: same-point fault deserves another attempt
    same_point_retries: int = 3
    backoff_base_s: float = 1.0
    backoff_factor: float = 2.0
    backoff_max_s: float = 60.0
    #: chunk-cap degradation steps, widest first, ending on the known-safe
    #: floor; degrade() moves to the first step below the current cap
    ch_max_ladder: tuple[int, ...] = dataclasses.field(
        default_factory=_default_ladder)
    #: initial cap (0 = uncapped until the first fetch-death)
    ch_max_start: int = 0
    #: consecutive clean chunks before the cap re-widens one step
    rewiden_after_clean_chunks: int = 32
    #: checkpoint cadence after a fault: halve, but never below the floor
    #: and never above the current cadence.  The floor stays WELL above 1:
    #: each checkpoint is a bulk _materialize fetch, and per-iteration
    #: fetches are both the pattern CLAUDE.md forbids and extra exposure to
    #: the very fetch-death class being retried.
    checkpoint_tighten_factor: int = 2
    checkpoint_every_min: int = 5

    def backoff_s(self, fault_index: int) -> float:
        """Exponential backoff for the (0-based) Nth fault."""
        return min(self.backoff_base_s * self.backoff_factor ** fault_index,
                   self.backoff_max_s)

    def next_checkpoint_every(self, every: int) -> int:
        """Tightened cadence: monotone non-increasing (a caller already
        below the floor keeps their cadence)."""
        return min(every, max(self.checkpoint_every_min,
                              every // self.checkpoint_tighten_factor))


class ChunkCapPolicy:
    """Live chunk-length cap: the supervisor degrades it on fetch-death
    faults; the trainer's clean-chunk feedback re-widens it."""

    def __init__(self, policy: RetryPolicy | None = None):
        self.policy = policy or RetryPolicy()
        if not self.policy.ch_max_ladder:
            raise ValueError("ch_max_ladder must have at least one step")
        # normalize: the walk logic assumes widest-first, but an ascending
        # user ladder (2, 4, 8) is a natural spelling — don't let it
        # silently invert degrade AND re-widen
        self._ladder = tuple(sorted(set(self.policy.ch_max_ladder),
                                    reverse=True))
        self._cap = int(self.policy.ch_max_start)
        self._clean = 0
        self._seen = 0        # longest chunk actually run (trainer feedback)
        self._fatal = 0       # shortest length a fault was observed AT (0 = none)
        #: whether the last degrade() actually stepped BELOW the length
        #: that was running — False means the remedy had no room left
        #: (fatal length already at/below the ladder floor); the
        #: supervisor journals it so "applied" and "exhausted" read apart
        self.last_shrunk = False
        #: whether a trainer ever consulted cap() — False means the run
        #: took a non-chunked path where degradation is a no-op; the
        #: supervisor journals this so an operator can tell "remedy
        #: applied" from "remedy inapplicable"
        self.consulted = False

    def cap(self) -> int:
        """Current cap on iterations per chunk; 0 = uncapped.  This is the
        TRAINER's entry point — reading it marks the cap as consulted."""
        self.consulted = True
        return self._cap

    def peek(self) -> int:
        """The cap without marking it consulted (supervisor observability)."""
        return self._cap

    def degrade(self) -> int:
        """Step the cap down the ladder, targeting the first step STRICTLY
        below what has actually been running (the observed chunk length
        when known — a ladder top at/above the calibrated CH would replay
        the fatal length unchanged).  Returns the new cap; resets the
        clean-chunk counter.  A cap already at/below the ladder floor
        (e.g. ch_max_start=1) is kept — degrading must never WIDEN chunks.
        """
        self._clean = 0
        self.last_shrunk = False
        floor = self._ladder[-1]
        # the reference length the next step must undercut: the SMALLER of
        # the current cap and the longest observed chunk (a cap above the
        # calibrated CH never governed what actually ran), else unbounded.
        # It is also remembered as FATAL — re-widening must never return
        # to a length a fault was observed at, or a persistent faulty
        # phase (the recorded r5 mode) would oscillate safe->fatal->safe,
        # burning the finite retry budget despite steady progress.
        ref = min([v for v in (self._cap, self._seen) if v], default=0)
        if ref:
            self._fatal = ref if self._fatal == 0 else min(self._fatal, ref)
        if self._cap != 0 and self._cap <= floor:
            return self._cap
        for step in self._ladder:
            if ref == 0 or step < ref:
                self._cap = step
                self.last_shrunk = True
                return self._cap
        # the fatal length is already at/below the floor: cap there anyway
        # (bounds future re-widening) but this did NOT shrink anything
        self._cap = floor
        return self._cap

    def note_dispatch(self, n: int) -> None:
        """Trainer feedback at DISPATCH time: a chunk of ``n`` iterations
        is about to be enqueued.  Recording the length here (not only on
        clean completion) is what makes the first degrade after a
        first-fetch death — the exact recorded r5 mode, where no chunk ever
        completed cleanly — step strictly below the fatal length."""
        if n:
            self._seen = max(self._seen, int(n))

    def note_clean_chunk(self, n: int = 0) -> None:
        """Trainer feedback: one chunk of ``n`` iterations completed its
        host work without a fault.  After ``rewiden_after_clean_chunks`` in
        a row the cap walks one ladder step back up (and past the top step,
        to uncapped)."""
        if n:
            self._seen = max(self._seen, int(n))
        if self._cap == 0:
            return
        self._clean += 1
        if self._clean < self.policy.rewiden_after_clean_chunks:
            return
        self._clean = 0
        # one ladder step back up, bounded STRICTLY below any known-fatal
        # length (never back to uncapped once a fatal length is on record)
        wider = [s for s in self._ladder
                 if s > self._cap and (self._fatal == 0 or s < self._fatal)]
        if wider:
            self._cap = wider[-1]
        elif self._fatal == 0:
            self._cap = 0
