"""Recompile tripwire: "zero recompiles after warmup" as a live alarm.

Two production invariants exist only as test assertions today: warm serve
traffic never recompiles (tests/test_serve.py, bench_serve --smoke), and
a training run's compiled programs are fixed once the first chunk has
dispatched (``p_key`` strips every field that cannot affect the program,
train.py).  A silent recompile of a wide program is not a slowdown but
an outage — minutes of compile wall mid-traffic — and the
fusion-shape change it implies is the near-tie argmax-flip class the
jaxpr auditor's digests guard offline.  This module is the ONLINE half:

* producers call ``note_compile(program, key)`` at each compile boundary
  (serve's compiled-entry cache on a cold key, the device trainer via
  engine/introspect.py);
* once the expected-compile budget is spent the producer calls
  ``arm(program)`` ("warmup complete / first chunk dispatched — nothing
  may compile again");
* a ``note_compile`` with a NEW key on an armed program increments
  ``dryad_recompile_unexpected_total{program=...}``, flips ``/healthz``
  to degraded (reason ``recompile``), and notifies listeners (the
  supervisor registers one that writes a ``recompile_unexpected`` event
  into the run journal).

``begin_program(program)`` resets a family for a new run/generation
(disarms, forgets keys, clears the degradation) — a second training run
or a rebuilt serve cache legitimately compiles fresh programs.

Obs contracts: host-side only (keys are hashable host values the caller
already holds — never an array), zero-cost when disabled (``note_compile``
returns after the enabled check; compile-boundary frequency anyway).
"""

from __future__ import annotations

import threading
from typing import Callable, Optional

from dryad_tpu.obs.health import HealthState, default_health
from dryad_tpu.obs.registry import Registry, default_registry

def health_reason(program: str) -> str:
    """The degradation key is scoped PER FAMILY: a training run beginning
    its own generation must never clear a co-located serve family's live
    recompile alarm (and vice versa)."""
    return f"recompile:{program}"


class RecompileTripwire:
    GUARDED_BY = {"_keys": "_lock", "_armed": "_lock",
                  "_listeners": "_lock"}

    def __init__(self, registry: Optional[Registry] = None,
                 health: Optional[HealthState] = None):
        self._registry = registry
        self._health = health
        self._lock = threading.Lock()
        self._keys: dict[str, set] = {}      # program -> seen keys
        self._armed: dict[str, bool] = {}
        self._listeners: list[Callable[[str, str], None]] = []

    def _reg(self) -> Registry:
        return (self._registry if self._registry is not None
                else default_registry())

    def _hp(self) -> HealthState:
        return self._health if self._health is not None else default_health()

    # ---- lifecycle ---------------------------------------------------------
    def begin_program(self, program: str) -> None:
        """A new run/generation of ``program`` starts: forget its keys,
        disarm, clear any standing degradation — for THIS family only."""
        with self._lock:
            self._keys[program] = set()
            self._armed[program] = False
        self._hp().clear(health_reason(program))

    def arm(self, program: str) -> None:
        """Expected-compile budget spent — any further NEW key on this
        program is an unexpected recompile.  Arming requires at least one
        NOTED key: with the registry disabled no keys are ever noted, and
        arming an empty family would turn a later mid-run ``enable()``
        (supported since r9) into a guaranteed false positive — an empty
        armed family cannot tell expected from unexpected, so it stays
        inert instead.  Arming also clears the family's standing
        degradation: re-warm + re-arm IS the documented recovery path
        after a deploy or a fired alarm."""
        with self._lock:
            if not self._keys.get(program):
                return
            self._armed[program] = True
        self._hp().clear(health_reason(program))

    def disarm(self, program: str) -> None:
        """Open a deploy window: a model load legitimately introduces new
        compiles, so the producer disarms (keeping the key history),
        warms the new programs, and re-arms via ``arm()``."""
        with self._lock:
            self._armed[program] = False
        self._hp().clear(health_reason(program))

    def armed(self, program: str) -> bool:
        with self._lock:
            return bool(self._armed.get(program))

    # ---- the boundary hook -------------------------------------------------
    def note_compile(self, program: str, key, detail: str = "") -> bool:
        """Record one compile boundary; returns True when the key is new.
        A new key on an ARMED program fires the tripwire."""
        reg = self._reg()
        if not reg.enabled:
            return False
        with self._lock:
            seen = self._keys.setdefault(program, set())
            new = key not in seen
            if new:
                seen.add(key)
            fired = new and self._armed.get(program, False)
        if new:
            reg.counter("dryad_prog_compiles_total",
                        "Compile boundaries by program family").labels(
                program=program).inc()
        if fired:
            self.unexpected(program, detail or f"new program key {key!r} "
                            "after warmup")
        return new

    def unexpected(self, program: str, detail: str = "") -> None:
        reg = self._reg()
        if reg.enabled:
            reg.counter("dryad_recompile_unexpected_total",
                        "Compiles observed after the expected-compile "
                        "budget was spent").labels(program=program).inc()
        self._hp().degrade(health_reason(program),
                           f"unexpected recompile in {program}: {detail}")
        with self._lock:
            listeners = list(self._listeners)
        for fn in listeners:
            try:
                fn(program, detail)
            except Exception:   # noqa: BLE001 — a dead listener must not
                pass            # break the producer's dispatch path

    # ---- listeners (the supervisor's journal hookup) -----------------------
    def add_listener(self, fn: Callable[[str, str], None]) -> Callable[[], None]:
        """Register ``fn(program, detail)`` for unexpected recompiles;
        returns a remover (duck-typed — the journal lives in resilience,
        which imports obs, so obs must not import it back)."""
        with self._lock:
            self._listeners.append(fn)

        def remove() -> None:
            with self._lock:
                if fn in self._listeners:
                    self._listeners.remove(fn)

        return remove


_default: Optional[RecompileTripwire] = None
_default_lock = threading.Lock()


def default_tripwire() -> RecompileTripwire:
    global _default
    if _default is None:
        with _default_lock:
            if _default is None:
                _default = RecompileTripwire()
    return _default
