"""Device time by ``dryad.*`` stage and idle time by ``train.*`` annotation.

The program names the stages of a boosting iteration with
``jax.named_scope`` (``dryad.grad``, ``dryad.hist``, ``dryad.route``,
``dryad.layout``, ``dryad.split_scan``, ``dryad.score``, ``dryad.eval``) and
puts its host spans on the profiler's clock (``train.chunk_dispatch``,
``train.fetch.checkpoint/materialize``, ...).  ``harness/trace.py`` keeps only
names and times of the device's events, and a reader gets ``facts`` and
nothing else, so this module goes back to the trace the runner just wrote:

* ``find_xplane`` finds it: the newest ``bench_*/trace/plugins/profile/*/
  *.xplane.pb`` under the temporary directory, written since this process
  started (the runner deletes its directory only after the readers ran);
* ``load`` reads the device's ``XLA Ops`` and ``XLA Modules`` and the host
  planes' ``train.*`` events.  No statistic of an operation's event carries
  its ``op_name`` (TPU v5 lite, jax 0.9.0: ``device_offset_ps``,
  ``device_duration_ps`` and ``Time Scale Multiplier`` are all there is), so
  the scope comes from the program: ``program_scope_maps`` asks
  ``dryad_tpu.engine.introspect.scope_maps()``, which read the compiled
  program's text at the compile boundary (``DRYAD_PROG_MEMORY=1``, which the
  runner sets), for ``{HLO module: {instruction: scope}}`` (the entry ``""``
  of a module is the scope of a small program that is one stage whole);
* ``reduce`` works on those lists and that map alone, with the leaf rule of
  ``trace.reduce``: an event ``%fusion.12 = ...`` inside the module event
  ``jit__chunk_jit(<fingerprint>)`` has the scope of instruction
  ``fusion.12`` of module ``jit__chunk_jit``.  It gives seconds per scope
  outside the kernels, kernel seconds per scope, and the idle seconds between
  programs by the innermost annotation that covers them.

``read()`` does all three once a process (eleven readers, one parse of 9 MB)
and prints two tables to standard error.  It returns ``{}`` where there is no
trace or no TPU plane in it (a CPU rehearsal): every reader then returns
``None``.  So does a reader whose names the program does not carry, as at a
commit before the scopes: no scope map, no ``train.`` annotation.

An operation belongs to the innermost ``dryad.*`` component of its
``op_name``.  A fusion is one operation and carries the ``op_name`` of its
root, so a fusion that spans two stages counts under its root's: the split is
exact in sum and approximate at the borders.  An instruction the compiler made
itself has no ``op_name``; the program gives it a neighbour's scope, marked
``~`` (``introspect._note_scopes`` says how), and ``inferred_s`` says how much
of each scope's time is of that kind.
"""

from __future__ import annotations

import bisect
import functools
import glob
import os
import sys
import tempfile

from benchmark.harness.trace import DEVICE_PLANE, MODULES_LINE, OPS_LINE, _union
from benchmark.layer_metrics.other_device_ms import KERNELS

HOST_PLANE = "/host:"
SPAN_PREFIX = "train."
UNSCOPED = "unscoped"
UNLABELLED = "unlabelled"
# a scope that starts so is a neighbour's: the compiler made the instruction
# (a copy, a rewritten reduction) and the program inferred where it belongs
INFERRED = "~"


def process_start_s() -> float:
    """Wall-clock time this process started (Linux ``/proc``); 0.0 where it
    cannot be read, which admits every trace."""
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/stat") as f:
            boot = next(int(ln.split()[1]) for ln in f if ln.startswith("btime"))
        return boot + ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, StopIteration, IndexError):
        return 0.0


def find_xplane(root: str | None = None, since_s: float | None = None) -> str | None:
    """The newest trace a runner of this process wrote, or None."""
    root = root or tempfile.gettempdir()
    since_s = process_start_s() if since_s is None else since_s
    paths = glob.glob(os.path.join(root, "bench_*", "trace", "plugins", "profile",
                                   "*", "*.xplane.pb"))
    # a second of grace: /proc counts the start in ticks since a boot time
    # that is given in whole seconds
    fresh = [(t, p) for t, p in ((os.path.getmtime(p), p) for p in paths)
             if t >= since_s - 1.0]
    return max(fresh)[1] if fresh else None


def program_scope_maps() -> dict:
    """``{HLO module name: {instruction name: scope}}`` as the program
    recorded it at its compile boundaries; ``{}`` from a program that keeps
    no such record."""
    try:
        from dryad_tpu.engine import introspect
    except ImportError:
        return {}
    return getattr(introspect, "scope_maps", dict)()


def load(path: str) -> dict:
    """``{"ops": [(plane, name, start_ns, dur_ns)], "modules": [(plane, name,
    start_ns, dur_ns)], "host": [(name, start_ns, dur_ns)]}``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    ops, modules, host = [], [], []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE):
            for line in plane.lines:
                if line.name in (OPS_LINE, MODULES_LINE):
                    (ops if line.name == OPS_LINE else modules).extend(
                        (plane.name, ev.name, int(ev.start_ns), int(ev.duration_ns))
                        for ev in line.events)
        elif plane.name.startswith(HOST_PLANE):
            for line in plane.lines:
                host += [(ev.name, int(ev.start_ns), int(ev.duration_ns))
                         for ev in line.events if ev.name.startswith(SPAN_PREFIX)]
    return {"ops": ops, "modules": modules, "host": host}


def short_name(event_name: str) -> str:
    """``%fusion.12 = f32[8] fusion(...)`` -> ``fusion.12``; ``jit_f(123)``
    -> ``jit_f``."""
    return event_name.split(" = ")[0].lstrip("%").split("(")[0]


def _leaves(ops: list[tuple]) -> list[tuple]:
    """The operations ``(start, end, name)`` that contain no other:
    ``trace.reduce``'s rule.  An operation that contains the next one (while,
    conditional, call) is no leaf: its time is its children's."""
    ops = sorted(ops)
    out = []
    for i, (s, e, name) in enumerate(ops):
        if i + 1 < len(ops) and ops[i + 1][0] < e and ops[i + 1][1] <= e \
                and (e - s) > (ops[i + 1][1] - ops[i + 1][0]):
            continue
        out.append((s, e, name))
    return out


def gaps_by(idle: list[tuple], spans: list[tuple]) -> dict:
    """Nanoseconds of the ``idle`` intervals by the innermost of ``spans``
    ``(name, start, end)`` that covers each part (the one that started last;
    spans of one thread nest), ``UNLABELLED`` where none does."""
    out: dict = {}
    for lo, hi in idle:
        near = [sp for sp in spans if sp[1] < hi and sp[2] > lo]
        cuts = sorted({lo, hi, *(min(max(t, lo), hi) for sp in near for t in sp[1:])})
        for a, b in zip(cuts, cuts[1:]):
            over = [sp for sp in near if sp[1] <= a and sp[2] >= b]
            name = max(over, key=lambda sp: (sp[1], -sp[2]))[0] if over else UNLABELLED
            out[name] = out.get(name, 0) + (b - a)
    return out


def reduce(loaded: dict, scope_maps: dict, kernels: dict[str, tuple] = KERNELS) -> dict:
    """Seconds, averaged over the devices: ``scope_s`` (leaf operations that
    no kernel needle matches, by scope, ``UNSCOPED`` for those in none),
    ``inferred_s`` (the part of ``scope_s`` whose scope is a neighbour's),
    ``kernel_s[group][scope]`` (the matched ones), ``unscoped_ops`` (the ten
    unscoped operations that took most time on the first device, each named
    ``module/instruction``), ``gap_s`` (idle between the first program's
    start and the last one's end, by annotation), ``scoped`` and
    ``annotated`` (whether the program carries any scope, any annotation)."""
    planes = sorted({op[0] for op in loaded["ops"]})
    if not planes:
        return {}
    per_plane = []
    for plane in planes:
        mods = sorted((s, s + d, short_name(n)) for p, n, s, d in loaded["modules"] if p == plane)
        starts = [m[0] for m in mods]
        scope_ns: dict = {}
        inferred_ns: dict = {}
        kernel_ns: dict = {g: {} for g in kernels}
        unscoped: dict = {}
        for s, e, name in _leaves([(s, s + d, n) for p, n, s, d in loaded["ops"] if p == plane]):
            short = short_name(name)
            at = bisect.bisect_right(starts, s) - 1
            module = mods[at][2] if at >= 0 and s < mods[at][1] else ""
            of_module = scope_maps.get(module, {})
            marked = of_module.get(short, of_module.get("", UNSCOPED))
            scope = marked.lstrip(INFERRED)
            group = next((g for g, needles in kernels.items()
                          if any(needle in short for needle in needles)), None)
            if group is not None:
                kernel_ns[group][scope] = kernel_ns[group].get(scope, 0) + (e - s)
                continue
            scope_ns[scope] = scope_ns.get(scope, 0) + (e - s)
            if marked != scope:
                inferred_ns[scope] = inferred_ns.get(scope, 0) + (e - s)
            if scope == UNSCOPED:
                key = module + "/" + short
                unscoped[key] = unscoped.get(key, 0) + (e - s)
        busy = _union([(s, e) for s, e, _ in mods])
        idle = [(a[1], b[0]) for a, b in zip(busy, busy[1:])]
        spans = [(n, s, s + d) for n, s, d in loaded["host"]]
        per_plane.append({"scope": scope_ns, "inferred": inferred_ns, "kernel": kernel_ns,
                          "unscoped": unscoped,
                          "gap": gaps_by(idle, spans)})

    def mean(dicts: list[dict]) -> dict:
        return {k: sum(d.get(k, 0) for d in dicts) / len(dicts) / 1e9
                for k in sorted({k for d in dicts for k in d})}

    top = sorted(per_plane[0]["unscoped"].items(), key=lambda kv: -kv[1])[:10]
    return {
        "devices": len(per_plane),
        "scope_s": mean([p["scope"] for p in per_plane]),
        "inferred_s": mean([p["inferred"] for p in per_plane]),
        "kernel_s": {g: mean([p["kernel"][g] for p in per_plane]) for g in kernels},
        "unscoped_ops": [[k, v / 1e9] for k, v in top],
        "gap_s": mean([p["gap"] for p in per_plane]),
        "scoped": any(scope_maps.values()),
        "annotated": bool(loaded["host"]),
    }


def _print_tables(path: str, r: dict) -> None:
    def say(msg):
        print(msg, file=sys.stderr)

    say(f"[scopes] {path}")
    total = sum(r["scope_s"].values()) + sum(sum(k.values()) for k in r["kernel_s"].values())
    say(f"[scopes] device seconds of leaf operations by scope ({r['devices']} device(s), "
        f"{total:.6f} s in all), the part of it with an inferred scope, kernels apart")
    for scope in sorted(set(r["scope_s"]) | {s for k in r["kernel_s"].values() for s in k}):
        kern = "  ".join(f"{g} {k[scope]:.6f}" for g, k in r["kernel_s"].items() if scope in k)
        say(f"[scopes]   {scope:<18} {r['scope_s'].get(scope, 0.0):12.6f} "
            f"{r['inferred_s'].get(scope, 0.0):12.6f}   {kern}")
    for name, s in r["unscoped_ops"]:
        say(f"[scopes]     unscoped: {name:<40} {s:.6f}")
    say("[scopes] idle seconds between programs by the annotation that covers them")
    for name, s in sorted(r["gap_s"].items(), key=lambda kv: -kv[1]):
        say(f"[scopes]   {name:<44} {s:.6f}")


@functools.lru_cache(maxsize=2)
def _read(path: str, mtime: float) -> dict:
    r = reduce(load(path), program_scope_maps())
    if r:
        _print_tables(path, r)
    return r


def read() -> dict:
    """The reduction of the trace this process's runner wrote; ``{}`` where
    there is none, or no TPU plane in it."""
    path = find_xplane()
    if path is None:
        return {}
    return _read(path, os.path.getmtime(path))


def device_ms_per_iter(facts: dict, *scopes: str):
    """Milliseconds an iteration of leaf operations in ``scopes`` that no
    kernel needle matches; None where the trace, the window or the program's
    scopes are missing."""
    r = read()
    if not r or not r["scoped"] or not facts.get("window_iters"):
        return None
    return 1000.0 * sum(r["scope_s"].get(s, 0.0) for s in scopes) / facts["window_iters"]


def span_mean_ms(facts: dict, path: str):
    """Mean wall, in milliseconds, of the program's spans in the window whose
    path ends in ``path`` (a supervisor puts its own span in front); None
    where the program records no such span."""
    walls = [dur for p, _, dur in facts["spans"] if p.endswith(path)]
    return 1000.0 * sum(walls) / len(walls) if walls else None
