"""Compiled-program introspection at compile boundaries (``dryad_prog_*``).

Host timing around one dispatch measures the enqueue, not the program
(CLAUDE.md measuring rules), so the per-program telemetry kept on the
dispatch path is what the compiler itself reports.  This module captures it — and it lives HERE,
in the engine, because it touches jax: the obs package is jax-free by
lint, and its registry contract says collectors only record values the
engine already fetched.  Everything recorded is a host scalar.

What happens at a compile boundary (``capture(family, key, jit_fn,
*args, **kwargs)``, called by engine/train.py, engine/predict.py and
serve/cache.py right before the FIRST dispatch of a program):

* ``dryad_prog_flops`` / ``dryad_prog_bytes_accessed`` gauges from
  ``jit_fn.lower(...).cost_analysis()`` — tracing + MLIR emission only,
  NO XLA compile, so the capture can never double the minutes a wide
  chunk program takes to compile.  On jax 0.9.0 and one device the jit
  call that follows takes this lowering, and the executable where one was
  compiled here, from jax's in-process caches: of the 7.945 s of a warm
  10M-row chunk 0 dispatch, 7.924 s are the capture's and 0.02 s the
  call's (PERF.md section 5, PR 36).  The lowering carries the capture's
  call stack, so its persistent-cache entry is not the one a run with the
  registry off (``DRYAD_OBS=0``: no capture, the call lowers itself)
  writes and reads.  On a mesh the call shares nothing: it lowers again
  and compiles or reads its own entry (four chips, 12M x 67: capture 91.9
  of the cold dispatch's 180.0 s, two misses; 5.4 of 9.6 s warm, two hits).
* ``dryad_prog_memory_bytes{kind=temp|argument|output}`` from
  ``compiled.memory_analysis()`` — this one NEEDS a real compile, so it
  is opt-in (``DRYAD_PROG_MEMORY=1``): the compile (or the persistent
  cache's read) then happens here, in the call's place on one device and
  beside the call's own on a mesh.  The same compile's HLO text gives
  ``scope_maps()``:
  which ``dryad.*`` stage each instruction of the program belongs to, by
  the instruction's name, for whoever reads a device trace of it.
* ``dryad_prog_compiles_total{program=...}`` via the recompile tripwire
  (obs/tripwire.py) — every boundary notes its program key there, so an
  armed family (serve after warmup, train after the first chunk) turns
  a NEW key into ``dryad_recompile_unexpected_total`` + a degraded
  ``/healthz``.
* ``dryad_prog_backend_compiles_total`` /
  ``dryad_prog_compile_seconds_total`` from a ``jax.monitoring``
  duration listener on the backend-compile event — the compile walls the
  runtime actually paid, process-wide, attributed to the boundary family
  that was active on the compiling thread (best-effort sticky label;
  compiles outside any declared boundary land on ``program="other"``).
  ``attributed(family)`` lends the label to a block of host code that
  compiles on its own account (``train.materialize``) and restores it;
  ``attribute(family)`` sets it for good (a job's entry) and
  ``attribute(None)`` clears it (the job's leave, by return or by raise).
* ``dryad_prog_jit_seconds_total{program, phase}`` from the same listener:
  what jit spent by family on ``trace`` (jaxpr tracing), ``lower`` (jaxpr to
  MLIR), ``backend_compile`` (jax's event around ``compile_or_get_cached``:
  the cache key, then the persistent cache's read on a hit, or XLA's
  compile and the cache's write on a miss) and ``cache_read`` (the read
  alone, which jax reports only for a hit and which lies inside that
  hit's ``backend_compile``).  ``dryad_prog_cache_total{program, result}``
  counts the persistent cache's ``hit`` and ``miss`` events (jax counts a
  miss where it writes an entry: a compile under the cache's thresholds
  of size and seconds is neither).

Cost model: captures are memoized per (family, key) process-wide, so a
warm re-run (bench arms, repeated serve traffic) pays NOTHING — exactly
mirroring the jit executable cache.  Every entry point returns after one
``enabled`` check when the registry is disabled (the zero-cost
contract), and a capture failure increments
``dryad_prog_capture_errors_total`` instead of breaking the dispatch.

dryadlint's ``introspect-compile-only`` rule pins the discipline: the
``cost_analysis``/``memory_analysis``/AOT-``compile()`` calls below are
the ONLY legal sites, and nothing here may be called from a loop body —
the tripwire must never become a per-iteration host sync.
"""

from __future__ import annotations

import contextlib
import math
import os
import re
import threading
from typing import Optional

from dryad_tpu.obs.registry import default_registry
from dryad_tpu.obs.spans import span
from dryad_tpu.obs.tripwire import default_tripwire

_seen: set = set()               # (family, key) already introspected
_seen_lock = threading.Lock()
_tls = threading.local()         # .program — sticky compile attribution
_listener_lock = threading.Lock()
_listener_installed = False

#: the jax.monitoring duration events of a jit call, by the last component
#: of the installed jax's event name -> the ``phase`` each is booked under
_PHASES = {"jaxpr_trace_duration": "trace",
           "jaxpr_to_mlir_module_duration": "lower",
           "backend_compile_duration": "backend_compile",
           "cache_retrieval_time_sec": "cache_read"}
#: the persistent cache's events, likewise -> ``result``
_CACHE_RESULTS = {"cache_hits": "hit", "cache_misses": "miss"}

#: the stages of a boosting iteration, as ``jax.named_scope`` names them
#: (README "Device truth"): a component of every operation's ``op_name``
SCOPE_PREFIX = "dryad."
#: marks a scope that the compiler's own instruction (a copy, a rewritten
#: reduction: no ``op_name``, or one that lost its stack) takes from the
#: instructions it reads or feeds
INFERRED = "~"
_scope_maps: dict = {}           # HLO module name -> {instruction: scope}
_HLO_MODULE = re.compile(r"HloModule ([\w.\-]+)")
_HLO_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\{$")
_HLO_INSTR = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = (.*)$")
_HLO_OP_NAME = re.compile(r'\bop_name="([^"]*)"')
# what an instruction's line names besides its operands: computations
# (``calls=%f``, ``body=%b``, ``branch_computations={%a, %b}``)
_HLO_CALLED = re.compile(r"\b\w+=(?:%[\w.\-]+|\{[^}]*\})")
_HLO_REF = re.compile(r"%([\w.\-]+)")
_HLO_DIMS = re.compile(r"\w+\[([\d,]*)\]")
_HLO_OPCODE = re.compile(r" ([\w\-]+)\(")


def memory_capture_enabled() -> bool:
    """Peak-memory capture costs one extra LOCAL compile per program —
    opt-in only (never silently doubles a minutes-long compile)."""
    return os.environ.get("DRYAD_PROG_MEMORY", "0") == "1"


def _on_duration(name: str, secs: float, **kw) -> None:
    phase = _PHASES.get(name.rpartition("/")[2])
    if phase is None:
        return
    reg = default_registry()
    if not reg.enabled:
        return
    program = getattr(_tls, "program", None) or "other"
    reg.counter("dryad_prog_jit_seconds_total",
                "Wall jit spent by boundary family and phase").labels(
        program=program, phase=phase).inc(float(secs))
    if phase != "backend_compile":
        return
    reg.counter("dryad_prog_backend_compiles_total",
                "Real XLA backend compiles by boundary family").labels(
        program=program).inc()
    reg.counter("dryad_prog_compile_seconds_total",
                "XLA backend compile wall by boundary family").labels(
        program=program).inc(float(secs))


def _on_event(name: str, **kw) -> None:
    result = _CACHE_RESULTS.get(name.rpartition("/")[2])
    if result is None:
        return
    reg = default_registry()
    if not reg.enabled:
        return
    reg.counter("dryad_prog_cache_total",
                "Persistent compile cache hits and misses by boundary "
                "family").labels(
        program=getattr(_tls, "program", None) or "other",
        result=result).inc()


def _install_listener() -> None:
    global _listener_installed
    if _listener_installed:
        return
    with _listener_lock:
        if _listener_installed:
            return
        import jax.monitoring

        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        jax.monitoring.register_event_listener(_on_event)
        _listener_installed = True


def attribute(family: Optional[str]) -> None:
    """Set this thread's sticky compile label to ``family`` until the next
    boundary (``capture``) or the next call changes it; ``None`` clears it,
    so that what the thread compiles next counts under ``other``.  A job
    sets its own name at its entry and clears the label in a ``finally``
    when it leaves: what runs jax on the thread after the job (the next
    job's set-up, a caller's own programs) is not the job's last family's."""
    if family is not None:
        if not default_registry().enabled:
            return
        _install_listener()
    _tls.program = family


@contextlib.contextmanager
def attributed(family: str):
    """Count this thread's backend compiles under ``family`` while the
    block runs, then give the sticky label back to the boundary that held
    it.  For host code that compiles small programs of its own between two
    boundaries (a checkpoint's slices): the listener's half of ``capture``
    with no key, no ``lower()`` and no tripwire note."""
    prev = getattr(_tls, "program", None)
    attribute(family)
    try:
        yield
    finally:
        _tls.program = prev


def _note_scopes(hlo_text: str) -> None:
    """Record ``{instruction name: innermost dryad.* scope}`` of one compiled
    program under its HLO module's name.  A device trace names each
    operation's event by the instruction (``%fusion.12 = ...``) inside the
    module's event (``jit__chunk_jit(<fingerprint>)``) and keeps nothing of
    ``op_name`` (TPU v5 lite, jax 0.9.0), so this is where the two meet.

    The compiler's own instructions (copies from layout assignment, loop
    plumbing, a cumsum rewritten to ``reduce_window_sum``) carry no scope.
    Each takes, marked ``INFERRED``, the scope of the largest operand that
    has one (the data it moves; the text is in schedule order, so one pass
    forward); failing that, of the last instruction that reads it (one pass
    backward); failing that, of the ``while`` or ``conditional`` that runs
    the computation it is in (a scatter the compiler turned into a loop).
    A scalar gives its scope to scalars only (one shared constant would
    else name a whole loop), and a tuple gives and takes none (it holds a
    loop's whole state)."""
    module = _HLO_MODULE.match(hlo_text)
    if module is None:
        return
    scopes: dict = {}
    order, reads, size, home, caller = [], {}, {}, {}, {}
    computation = ""
    for line in hlo_text.splitlines():
        header = _HLO_COMPUTATION.match(line)
        if header is not None:
            computation = header.group(1)
            continue
        found = _HLO_INSTR.match(line)
        opcode = _HLO_OPCODE.search(found.group(2)) if found else None
        if opcode is None or opcode.group(1) == "tuple":
            continue
        instr, rest = found.groups()
        shape, args = rest[:opcode.start()], rest[opcode.end():]
        order.append(instr)
        home[instr] = computation
        reads[instr] = _HLO_REF.findall(_HLO_CALLED.sub("", args))
        size[instr] = max((math.prod(int(n) for n in dims.split(",") if n)
                           for dims in _HLO_DIMS.findall(shape)), default=1)
        if opcode.group(1) in ("while", "conditional", "call"):
            for called in _HLO_CALLED.findall(args):
                caller.update((c, instr) for c in _HLO_REF.findall(called))
        op_name = _HLO_OP_NAME.search(rest)
        for part in reversed(op_name.group(1).split("/") if op_name else ()):
            if part.startswith(SCOPE_PREFIX):
                scopes[instr] = part
                break

    def inferred(instr):
        return INFERRED + scopes[instr].lstrip(INFERRED)

    for instr in order:
        if instr not in scopes:
            known = [o for o in reads[instr] if o in scopes
                     and (size[o] > 1 or size[instr] <= 1)]
            if known:
                scopes[instr] = inferred(max(known, key=size.__getitem__))
    for instr in reversed(order):
        for o in reads[instr]:
            if o not in scopes and instr in scopes and o in reads \
                    and (size[instr] > 1 or size[o] <= 1):
                scopes[o] = inferred(instr)
    for instr in reversed(order):         # callers come after their callees
        if instr not in scopes and caller.get(home[instr]) in scopes:
            scopes[instr] = inferred(caller[home[instr]])
    with _seen_lock:
        _scope_maps[module.group(1)] = scopes


def whole_program(scope: str, jit_fn):
    """Declare the jitted ``jit_fn`` one stage whole (its body runs under a
    single ``jax.named_scope(scope)``): the small programs the per-iteration
    path dispatches beside the step program have no compile boundary of
    their own, so their map is the one entry ``""``, every instruction.
    Returns ``jit_fn``."""
    with _seen_lock:
        _scope_maps["jit_" + jit_fn.__name__] = {"": scope}
    return jit_fn


def scope_maps() -> dict:
    """``{HLO module name: {instruction name: dryad.* scope}}`` of every
    program whose compiled text a boundary has read (``DRYAD_PROG_MEMORY=1``)
    or that was declared one stage whole (``{"": scope}``): what joins a
    device trace's events to the stages.  A scope that starts with
    ``INFERRED`` is a neighbour's, not the instruction's own."""
    with _seen_lock:
        return {name: dict(scopes) for name, scopes in _scope_maps.items()}


def seen(family: str, key) -> bool:
    with _seen_lock:
        return (family, key) in _seen


def reset_seen() -> None:
    """Forget the process memo (tests re-capture after clear_caches)."""
    with _seen_lock:
        _seen.clear()


def capture(family: str, key, jit_fn, *args,
            labels: Optional[dict] = None, note_tripwire: bool = True,
            **kwargs) -> bool:
    """Introspect one compile boundary; returns True when (family, key)
    was new and a capture ran.  ``jit_fn``/``args``/``kwargs`` must be
    EXACTLY what the caller is about to dispatch — the lowering is the
    program the jit call will compile.  Observation-only: the jit call
    path, and therefore every traced program, is untouched (the jaxpr
    auditor's digests are the proof)."""
    reg = default_registry()
    if not reg.enabled:
        return False
    if os.environ.get("DRYAD_PROG", "1") == "0":
        # operational kill switch: the capture's lower() doubles a
        # program's TRACE cost (never its compile) — skippable where even
        # that matters, without disabling the rest of the registry
        return False
    # sticky attribution for the compile the caller is about to trigger
    attribute(family)
    if note_tripwire:
        default_tripwire().note_compile(family, key)
    with _seen_lock:
        if (family, key) in _seen:
            return False
        _seen.add((family, key))
    lbl = dict(labels or {})
    lbl["program"] = family
    try:
        # the boundary's own wall (the lowering and, opted in, the compile
        # or cache read and the HLO text's scope map; on one device the
        # call that follows finds them done) on the spans' clock, under
        # whatever span is open: train.chunk_dispatch/capture
        with span("capture"):
            lowered = jit_fn.lower(*args, **kwargs)
            cost = lowered.cost_analysis()
            d = cost[0] if isinstance(cost, (list, tuple)) else (cost or {})
            if "flops" in d:
                reg.gauge("dryad_prog_flops",
                          "Compiler flops estimate per program").labels(
                    **lbl).set(float(d["flops"]))
            if "bytes accessed" in d:
                reg.gauge("dryad_prog_bytes_accessed",
                          "Compiler bytes-accessed estimate per "
                          "program").labels(
                    **lbl).set(float(d["bytes accessed"]))
            if memory_capture_enabled():
                compiled = lowered.compile()
                ma = compiled.memory_analysis()
                mem = reg.gauge("dryad_prog_memory_bytes",
                                "Compiled-program memory estimate by kind")
                for kind, attr in (("temp", "temp_size_in_bytes"),
                                   ("argument", "argument_size_in_bytes"),
                                   ("output", "output_size_in_bytes")):
                    val = getattr(ma, attr, None)
                    if val is not None:
                        mem.labels(kind=kind, **lbl).set(float(val))
                _note_scopes(compiled.as_text())
        reg.counter("dryad_prog_captures_total",
                    "Successful compile-boundary introspections").labels(
            program=family).inc()
    except Exception:   # noqa: BLE001 — introspection must never break
        reg.counter("dryad_prog_capture_errors_total",   # the dispatch
                    "Compile-boundary introspections that raised").labels(
            program=family).inc()
    return True
