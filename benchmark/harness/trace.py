"""From a profiler trace to busy time, kernel time by name, and idle gaps.

``load_xplane`` turns the ``.xplane.pb`` that ``jax.profiler`` writes into a
flat list of events ``(plane, line, name, start_ns, dur_ns)``, with nothing
but jax.  ``reduce`` works on that list alone, so it is checked against a
small recorded list committed with the tests.

On a TPU the device plane is ``/device:TPU:<n>``.  Its line ``XLA Ops``
holds one event for each execution of an HLO operation (a ``while`` and the
operations of its body both appear; busy time is the union of intervals, so
nesting is harmless), and ``XLA Modules`` one event for each run of a
compiled program.  An operation's event is named by its whole HLO line,
``%name = type op(operands)``; only ``name`` is matched, because operands
name other operations.  The Pallas kernels carry no ``name=``: their custom
calls are named after the jitted function that wraps the ``pallas_call``
(``_hist_tiles.35``, ``permute_records.1``), and a kernel group is matched
by such substrings.
"""

from __future__ import annotations

import glob
import os

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def _traces(trace_dir: str) -> list[str]:
    return sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))


def load_xplane(trace_dir: str) -> list[tuple]:
    """Events of the newest trace under ``trace_dir`` on device planes."""
    from jax.profiler import ProfileData

    paths = _traces(trace_dir)
    if not paths:
        return []
    data = ProfileData.from_file(paths[-1])
    events = []
    for plane in data.planes:
        if not plane.name.startswith(DEVICE_PLANE):
            continue
        for line in plane.lines:
            if line.name not in (OPS_LINE, MODULES_LINE):
                continue
            for ev in line.events:
                events.append((plane.name, line.name, ev.name,
                               int(ev.start_ns), int(ev.duration_ns)))
    return events


def _union(intervals: list[tuple]) -> list[tuple]:
    merged: list[list] = []
    for lo, hi in sorted(intervals):
        if merged and lo <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], hi)
        else:
            merged.append([lo, hi])
    return [(lo, hi) for lo, hi in merged]


def reduce(events: list[tuple], kernels: dict[str, tuple]) -> dict:
    """Busy time, program time and kernel time of a trace, per device,
    averaged over the devices.

    ``kernels`` maps a group name to substrings of operation names.  Returns
    seconds: ``span_s`` (first operation's start to the last one's end),
    ``busy_s`` (union of the operations' intervals), ``programs_s`` (union of
    the compiled programs' intervals: what is outside it is idle between
    programs), per kernel group ``kernel_s``, and ``top_ops``, the ten
    operations that took most time on the first device, containers such as
    ``while`` left out."""
    planes = sorted({e[0] for e in events})
    per_plane = []
    for plane in planes:
        ops = sorted((s, s + d, n) for p, line, n, s, d in events
                     if p == plane and line == OPS_LINE)
        mods = [(s, s + d) for p, line, n, s, d in events if p == plane and line == MODULES_LINE]
        if not ops:
            continue
        busy_ns = sum(e - s for s, e in _union([(s, e) for s, e, _ in ops]))
        kernel_ns = {k: 0 for k in kernels}
        by_name: dict = {}
        for i, (s, e, n) in enumerate(ops):
            # an operation that contains the next one (while, conditional,
            # call) is no leaf: its time is its children's
            if i + 1 < len(ops) and ops[i + 1][0] < e and ops[i + 1][1] <= e \
                    and (e - s) > (ops[i + 1][1] - ops[i + 1][0]):
                continue
            short = n.split(" = ")[0].lstrip("%")
            by_name[short] = by_name.get(short, 0) + (e - s)
            for group, needles in kernels.items():
                if any(needle in short for needle in needles):
                    kernel_ns[group] += e - s
        per_plane.append({
            "span_s": (max(e for _, e, _ in ops) - ops[0][0]) / 1e9,
            "busy_s": busy_ns / 1e9,
            "programs_s": sum(e - s for s, e in _union(mods)) / 1e9,
            "programs": len(mods),
            "kernel_s": {k: v / 1e9 for k, v in kernel_ns.items()},
            "by_name": by_name,
        })
    if not per_plane:
        return {}
    n = len(per_plane)
    out = {"devices": n, "programs": per_plane[0]["programs"]}
    for key in ("span_s", "busy_s", "programs_s"):
        out[key] = sum(p[key] for p in per_plane) / n
    out["kernel_s"] = {k: sum(p["kernel_s"][k] for p in per_plane) / n for k in kernels}
    top = sorted(per_plane[0]["by_name"].items(), key=lambda kv: -kv[1])[:10]
    out["top_ops"] = [[k, v / 1e9] for k, v in top]
    return out
