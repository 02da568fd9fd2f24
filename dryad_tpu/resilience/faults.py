"""Fault classification + deterministic injection for supervised runs.

The classes named here are the failure modes actually RECORDED in this
repo's long runs (rounds 4-5), not a hypothetical list:

* **fetch_death** — a device->host fetch pending for minutes behind
  queued work surfaces as an ``UNAVAILABLE: TPU device error`` /
  ``worker process crashed`` at the fetch site (recorded: 6/6 first-fetch
  deaths on ~20 s chunks while ``DRYAD_CH_MAX=2`` runs always passed).
  The remedy is chunk
  degradation (resilience/policy.py), which is why this class is split
  from the generic device error even though the message family overlaps —
  the distinguishing signal is the SITE the error was raised at, which the
  supervisor tracks through the trainer's ``chunk_hook``.
* **device_unavailable** — the same ``UNAVAILABLE`` family raised away
  from a fetch (dispatch-time device loss, worker crash, connection
  reset).  Remedy: plain resume from the latest checkpoint.
* **oom** — ``RESOURCE_EXHAUSTED`` / "out of memory" allocations.
* **preemption** — ``ABORTED`` / "preempted" worker revocations.
* **unknown** — everything else.  The supervisor FAILS CLOSED on these:
  retrying an unrecognized error hides real bugs behind checkpoints.

Classification matches on exception type family (RuntimeError/OSError —
jaxlib's ``XlaRuntimeError`` is a RuntimeError subclass) plus the recorded
message signatures, so the injected faults below and the real runtime's
errors classify identically.

``FaultInjector`` is the deterministic injection layer: it IS a
``chunk_hook`` (engine/train.py, cpu/trainer.py) and raises the real error
classes at configured (site, iteration) points, so every resilience path
runs under ``JAX_PLATFORMS=cpu`` in tier-1.  Not passing one costs
nothing — the trainers skip the hook entirely when it is None.

Replica-level kinds (r14, the serving-fleet drills): the same injector
shape doubles as a SERVE-process hook — the HTTP front end calls it at
``("request", n)`` per /predict and ``("health", n)`` per /healthz probe
(serve/http.py), with the points wired through the environment
(``DRYAD_REPLICA_FAULTS``; encode/decode below) so a fleet supervisor can
arm drills in subprocess replicas it spawns:

* **replica_crash** — the process hard-exits (``os._exit(REPLICA_CRASH_EXIT)``,
  no cleanup) at the configured point: the deterministic twin of a
  segfault/OOM-kill, used to test crash detection + respawn.
* **slow_health** — the hook sleeps ``stall_s`` at the point (usually the
  ``health`` site) and then proceeds: a probe that exceeds its timeout,
  the hang-detection twin.
* **reject_503** — raises ``InjectedReject``, which the HTTP front end
  maps to a 503 answer at that site (a replica stuck shedding, the
  stuck-503 twin).  Mark the point ``sticky`` for the latched form.

These are injection KINDS, not classification classes: ``classify_fault``
never returns them (a fleet supervisor observes replica death through the
process exit code / probe, not through a raised exception).
"""

from __future__ import annotations

import dataclasses
import re

FETCH_DEATH = "fetch_death"
DEVICE_UNAVAILABLE = "device_unavailable"
OOM = "oom"
PREEMPTION = "preemption"
UNKNOWN = "unknown"
#: not a fault CLASS but an injection KIND (r12): the injector SLEEPS at
#: the configured hook site instead of raising — the deterministic twin
#: of a fetch hanging behind queued device work, used to test the
#: obs fetch-stall watchdog (the hook fires inside the trainer's
#: watch_fetch bracket, so the in-flight age gauge sees the hang)
STALL = "stall"

#: replica-level injection KINDS (r14; see module docstring) — executed by
#: the injector / the serve HTTP front end, never returned by classify_fault
REPLICA_CRASH = "replica_crash"
SLOW_HEALTH = "slow_health"
REJECT_503 = "reject_503"
REPLICA_KINDS = (REPLICA_CRASH, SLOW_HEALTH, REJECT_503)

#: continual-boosting injection KIND (r19): the retrain worker consults
#: the injector via ``take()`` at its ``("retrain", job_index)`` point and,
#: when armed, trains the generation against the WRONG data distribution —
#: the deterministic twin of a poisoned retrain data pipeline, used to
#: drill the probation auto-rollback (continual/publish.py).  Action-at-
#: caller: ``take()`` RETURNS the fired point instead of raising, because
#: the drill needs a structurally valid (merely drift-breaching) model.
BAD_GENERATION = "bad_generation"
CONTINUAL_KINDS = (BAD_GENERATION,)
#: the exit code an injected replica_crash dies with — fleet tests and the
#: ci smoke identify the injected death by it (any OTHER nonzero exit in a
#: drill is a real bug, not the drill)
REPLICA_CRASH_EXIT = 23

#: classes the supervisor may retry; UNKNOWN always fails closed
RETRYABLE = (FETCH_DEATH, DEVICE_UNAVAILABLE, OOM, PREEMPTION)

#: the site vocabulary of the trainers' chunk_hook
SITES = ("dispatch", "fetch")
#: the site vocabulary of the serve front end's replica fault hook
REPLICA_SITES = ("request", "health")
#: the site vocabulary of the continual retrain worker's fault hook (r19)
CONTINUAL_SITES = ("retrain",)


class InjectedReject(RuntimeError):
    """The REJECT_503 drill: the HTTP front end answers 503 at this site.
    Deliberately NOT classifiable (classify_fault -> UNKNOWN): a drilled
    rejection must never be mistaken for a recorded device fault class."""

_OOM_PAT = re.compile(r"RESOURCE_EXHAUSTED|out of memory|hbm.*exceeds",
                      re.IGNORECASE)
# "preempt" in any casing, but the grpc status token only as the exact
# uppercase word — prose like "compilation aborted" must NOT classify as
# a retryable preemption (it would burn the retry budget on a real bug)
_PREEMPT_PAT = re.compile(r"(?i:preempt)|\bABORTED\b")
_UNAVAILABLE_PAT = re.compile(
    r"UNAVAILABLE|TPU device error|worker process crashed"
    r"|socket closed|connection reset", re.IGNORECASE)
# a fetch death announced in the message itself (deadline class) — site
# information is then not required to classify it
_FETCH_PAT = re.compile(r"DEADLINE_EXCEEDED|fetch.*(timed out|killed)",
                        re.IGNORECASE)


def classify_fault(exc: BaseException, at_fetch: bool = False) -> str:
    """Map a raised exception onto the recorded fault classes.

    ``at_fetch`` says whether the trainer's last chunk_hook event before
    the raise was a ``"fetch"`` site — the supervisor tracks this; it is
    what splits fetch_death from device_unavailable for the overlapping
    ``UNAVAILABLE`` message family (see module docstring).
    """
    # only runtime-shaped errors can be device faults: a ValueError from
    # config validation (or any non-Exception) must never be retried
    if not isinstance(exc, (RuntimeError, OSError)):
        return UNKNOWN
    msg = f"{type(exc).__name__}: {exc}"
    if _OOM_PAT.search(msg):
        return OOM
    if _PREEMPT_PAT.search(msg):
        return PREEMPTION
    if _FETCH_PAT.search(msg):
        return FETCH_DEATH
    if _UNAVAILABLE_PAT.search(msg):
        return FETCH_DEATH if at_fetch else DEVICE_UNAVAILABLE
    return UNKNOWN


# the messages injection raises — the real signatures from STATUS r5, so
# classify_fault treats injected and genuine faults identically
_CANONICAL_MSG = {
    # "fetch ... killed" matches _FETCH_PAT, so the injected exception
    # classifies as fetch_death by MESSAGE alone — make_fault's contract
    # ("classifies as kind") holds at any site.  Real fetch deaths carry
    # no such token and rely on the supervisor's fetch-site attribution.
    FETCH_DEATH: ("UNAVAILABLE: TPU device error: worker process crashed "
                  "(fetch pending >60s behind queued work killed) "
                  "[injected]"),
    DEVICE_UNAVAILABLE: "UNAVAILABLE: TPU device error [injected]",
    OOM: ("RESOURCE_EXHAUSTED: out of memory while trying to allocate "
          "device buffer [injected]"),
    PREEMPTION: "ABORTED: the TPU worker was preempted [injected]",
    UNKNOWN: "injected fault with no recorded signature",
}

_ERROR_CLS = None


def _error_class():
    """The real jaxlib error type when constructible (it subclasses
    RuntimeError), else RuntimeError — classification only reads the
    message, so both exercise identical supervisor paths."""
    global _ERROR_CLS
    if _ERROR_CLS is None:
        try:
            from jaxlib.xla_extension import XlaRuntimeError

            XlaRuntimeError("constructibility probe")
            _ERROR_CLS = XlaRuntimeError
        except Exception:
            _ERROR_CLS = RuntimeError
    return _ERROR_CLS


def make_fault(kind: str) -> BaseException:
    """An exception instance that classifies as ``kind`` (UNKNOWN included:
    its message matches no recorded signature)."""
    if kind not in _CANONICAL_MSG:
        raise ValueError(f"unknown fault kind {kind!r}")
    return _error_class()(_CANONICAL_MSG[kind])


@dataclasses.dataclass(frozen=True)
class FaultPoint:
    """One configured injection: fire at the FIRST chunk-hook event with
    ``site`` at/after ``iteration`` (>=, not ==: chunked dispatch only
    visits chunk-start iterations, so an exact match could never hit).
    ``kind=STALL``/``SLOW_HEALTH`` sleeps ``stall_s`` seconds at the hook
    instead of raising (the hung-fetch / slow-probe twins; the run then
    proceeds normally).  ``sticky=True`` keeps the point armed after it
    fires — the latched form the stuck-503 drill needs (a replica that
    sheds ONE request is a blip; one that sheds every request from a
    point on is the recorded failure shape)."""

    iteration: int
    kind: str = DEVICE_UNAVAILABLE
    site: str = "dispatch"
    stall_s: float = 0.0
    sticky: bool = False

    def __post_init__(self):
        all_sites = SITES + REPLICA_SITES + CONTINUAL_SITES
        if self.site not in all_sites:
            raise ValueError(f"site must be one of {all_sites}, "
                             f"got {self.site!r}")
        if (self.kind not in (STALL,) + REPLICA_KINDS + CONTINUAL_KINDS
                and self.kind not in _CANONICAL_MSG):
            raise ValueError(f"unknown fault kind {self.kind!r}")
        # kinds and sites partition strictly: a replica kind at a trainer
        # site would never fire (or worse, os._exit a training run), and a
        # trainer class at a replica site decodes cleanly but arms nothing —
        # both are the silent-typo'd-drill shape that must fail loudly
        if self.kind in REPLICA_KINDS and self.site not in REPLICA_SITES:
            raise ValueError(
                f"replica fault kind {self.kind!r} fires only at replica "
                f"sites {REPLICA_SITES}, got site {self.site!r}")
        if self.kind in CONTINUAL_KINDS and self.site not in CONTINUAL_SITES:
            raise ValueError(
                f"continual fault kind {self.kind!r} fires only at "
                f"continual sites {CONTINUAL_SITES}, got site {self.site!r}")
        if (self.kind not in REPLICA_KINDS
                and self.site in REPLICA_SITES):
            raise ValueError(
                f"fault kind {self.kind!r} is a trainer class and never "
                f"fires at replica site {self.site!r}; use one of "
                f"{REPLICA_KINDS}")
        if (self.kind not in CONTINUAL_KINDS
                and self.site in CONTINUAL_SITES):
            raise ValueError(
                f"fault kind {self.kind!r} never fires at continual site "
                f"{self.site!r}; use one of {CONTINUAL_KINDS}")
        if self.kind in (STALL, SLOW_HEALTH) and self.stall_s <= 0:
            raise ValueError(f"a {self.kind} point needs stall_s > 0")


class FaultInjector:
    """Deterministic fault injection, shaped as a trainer ``chunk_hook``.

    Each point fires EXACTLY ONCE per injector lifetime — the supervisor
    keeps one injector across retries, so a resumed segment replays past
    the already-fired point instead of dying on it again.  ``fired``
    records (point index, site, iteration, kind) for test assertions.

    Lock contract (r15): ``_lock`` (declared below) makes the armed
    check-and-clear atomic — the serve front end calls the hook from
    ThreadingHTTPServer handler threads, and without the lock a one-shot
    drill fired once per in-flight request (found by the r14 review,
    now pinned by the schedule harness's concurrent-fire drill).  The
    fault ACTIONS (sleep, raise, os._exit) run strictly OUTSIDE the
    lock: a SLOW_HEALTH stall must hold up only its own probe, never
    serialize concurrent injections — the no-blocking-under-lock lint
    keeps it that way.
    """

    GUARDED_BY = {"_armed": "_lock", "fired": "_lock"}

    def __init__(self, points):
        import threading

        self.points = [p if isinstance(p, FaultPoint) else FaultPoint(*p)
                       for p in points]
        self._armed = [True] * len(self.points)
        self.fired: list[dict] = []
        # the serve front end calls the hook from ThreadingHTTPServer
        # handler threads: the armed check-and-clear must be atomic or a
        # one-shot drill fires once per in-flight request (the trainer
        # path is single-threaded and pays one uncontended acquire)
        self._lock = threading.Lock()

    def __call__(self, site: str, iteration: int) -> None:
        to_fire: list[FaultPoint] = []
        with self._lock:
            for i, pt in enumerate(self.points):
                if (self._armed[i] and site == pt.site
                        and iteration >= pt.iteration):
                    if not pt.sticky:
                        self._armed[i] = False
                    self.fired.append({"point": i, "site": site,
                                       "iteration": int(iteration),
                                       "kind": pt.kind})
                    to_fire.append(pt)
                    if pt.kind not in (STALL, SLOW_HEALTH):
                        # a raising/exiting point ends THIS call's scan:
                        # later points stay armed for later events (three
                        # identical points = three successive faults, the
                        # repeated-same-point drill)
                        break
        # actions run OUTSIDE the lock: a SLOW_HEALTH sleep must stall
        # only its own probe, never serialize concurrent injections
        for pt in to_fire:
            if pt.kind in CONTINUAL_KINDS:
                # action-at-caller kinds are consumed via take(); firing
                # one through the raising hook is a drill wiring bug —
                # doing nothing here would silently disarm it
                raise ValueError(
                    f"{pt.kind} is an action-at-caller kind: consume it "
                    "with FaultInjector.take(), not the raising hook")
            if pt.kind in (STALL, SLOW_HEALTH):
                # a hang, not a death: hold the hook (inside the
                # trainer's watch_fetch bracket / the replica's probe
                # handler) so the watcher sees the latency rise, then
                # let the run continue
                import time

                time.sleep(pt.stall_s)
                continue
            if pt.kind == REPLICA_CRASH:
                # the deterministic twin of a segfault/OOM-kill: no
                # atexit, no flushes — the fleet supervisor must see
                # exactly what a real crash leaves behind
                import os

                os._exit(REPLICA_CRASH_EXIT)
            if pt.kind == REJECT_503:
                raise InjectedReject(
                    f"injected 503 rejection at {site} #{iteration}")
            raise make_fault(pt.kind)

    def take(self, site: str, iteration: int) -> "FaultPoint | None":
        """Atomic check-and-clear for ACTION-AT-CALLER kinds (r19
        ``bad_generation``): returns the first matching armed point
        (recorded in ``fired``) instead of raising/exiting — the caller
        owns the fault's effect.  Same one-shot/sticky discipline as
        ``__call__``; the two share ``_armed``, so a point consumed here
        can never also fire there."""
        with self._lock:
            for i, pt in enumerate(self.points):
                if (self._armed[i] and site == pt.site
                        and iteration >= pt.iteration):
                    if not pt.sticky:
                        self._armed[i] = False
                    self.fired.append({"point": i, "site": site,
                                       "iteration": int(iteration),
                                       "kind": pt.kind})
                    return pt
        return None

    @property
    def pending(self) -> int:
        with self._lock:
            return sum(self._armed)


# ---------------------------------------------------------------------------
# environment wire format (fleet drills -> subprocess replicas)
#
# A fleet supervisor arms drills in replicas it SPAWNS, so the points must
# survive an exec boundary: one env var, ``DRYAD_REPLICA_FAULTS``, holding
# comma-separated ``site:iteration:kind[:stall_s][:sticky]`` specs —
# e.g. ``request:3:replica_crash`` or ``health:1:slow_health:6.0:sticky``.
# The serve CLI decodes it at startup and threads the injector into the
# HTTP front end's fault hook; an absent/empty var costs nothing.

REPLICA_FAULTS_ENV = "DRYAD_REPLICA_FAULTS"
#: same wire format, consumed by the continual retrain worker (r19) — the
#: scheduler passes it through the subprocess env so a forced-bad-
#: generation drill survives the exec boundary like the replica drills do
CONTINUAL_FAULTS_ENV = "DRYAD_CONTINUAL_FAULTS"


def encode_points(points) -> str:
    """``FaultPoint``s (or their tuple spellings) -> the env-var string."""
    specs = []
    for p in points:
        if not isinstance(p, FaultPoint):
            p = FaultPoint(*p)
        spec = f"{p.site}:{p.iteration}:{p.kind}"
        if p.stall_s:
            spec += f":{p.stall_s}"
        if p.sticky:
            spec += ":sticky" if p.stall_s else ":0:sticky"
        specs.append(spec)
    return ",".join(specs)


def decode_points(value: str) -> list[FaultPoint]:
    """The env-var string -> validated ``FaultPoint``s (raises ValueError
    on malformed specs: a typo'd drill must fail loudly at replica start,
    not silently arm nothing)."""
    points = []
    for spec in (value or "").split(","):
        spec = spec.strip()
        if not spec:
            continue
        parts = spec.split(":")
        if len(parts) < 3:
            raise ValueError(f"malformed replica fault spec {spec!r} "
                             "(want site:iteration:kind[:stall_s][:sticky])")
        sticky = False
        if parts[-1] == "sticky":
            sticky = True
            parts = parts[:-1]
        if len(parts) > 4:
            # a misspelt "sticky" (or any extra token) must not silently
            # arm the non-latched form of the drill
            raise ValueError(
                f"malformed replica fault spec {spec!r}: unrecognized "
                f"trailing field {parts[4]!r} "
                "(want site:iteration:kind[:stall_s][:sticky])")
        stall_s = float(parts[3]) if len(parts) > 3 else 0.0
        points.append(FaultPoint(site=parts[0], iteration=int(parts[1]),
                                 kind=parts[2], stall_s=stall_s,
                                 sticky=sticky))
    return points


def injector_from_env(environ=None,
                      env_var: str = REPLICA_FAULTS_ENV
                      ) -> "FaultInjector | None":
    """Build an injector from the named env var (default: the replica
    drills' ``DRYAD_REPLICA_FAULTS``; the continual retrain worker passes
    ``CONTINUAL_FAULTS_ENV``).  None when unset/empty — the production
    path."""
    import os

    value = (environ if environ is not None else os.environ).get(env_var, "")
    points = decode_points(value)
    return FaultInjector(points) if points else None
