"""What the program's own registry says of set-up: span walls and jit seconds.

The profiler opens with the window, so set-up is on no device trace; the
program's spans (``data.sketch``, ``train.setup/upload``, ``capture``) and its
``jax.monitoring`` listener (``dryad_prog_jit_seconds_total{program, phase}``)
are read from the process's registry after the job, over the whole run.  A
series that is absent reads 0.0: the work did not happen (no native build, no
cache hit), or the program is from before the series.  Nothing here needs a
device: a CPU rehearsal reads the same numbers.
"""

from __future__ import annotations

import re

SPAN_SECONDS = "dryad_span_seconds_total"
JIT_SECONDS = "dryad_prog_jit_seconds_total"
# families whose compiles are not set-up's: a checkpoint's slices are the
# window's (``ckpt_compiles``, ``window_compiles``), and ``other`` is what runs
# on the thread once the job has left (the reference)
NOT_SETUP = ("other", "train.materialize")

_LABEL = re.compile(r'(\w+)="([^"]*)"')


def _series(name: str) -> list:
    """``[({label: value}, number)]`` of one counter family of the registry."""
    from dryad_tpu.obs.registry import default_registry

    family = default_registry().snapshot()["counters"].get(name, {})
    return [(dict(_LABEL.findall(str(lbl))), float(v)) for lbl, v in family.items()]


def span_seconds(path: str) -> float:
    """Total wall of the spans whose path is ``path`` or ends in ``/path`` (a
    caller's span in front of it)."""
    return sum((v for lbl, v in _series(SPAN_SECONDS)
                if ("/" + lbl.get("span", "")).endswith("/" + path)), 0.0)


def jit_seconds(*phases: str) -> float:
    """Seconds jit spent in ``phases`` for the job's own program families."""
    return sum((v for lbl, v in _series(JIT_SECONDS)
                if lbl.get("phase") in phases and lbl.get("program") not in NOT_SETUP), 0.0)
