"""Dispatch gates: every threshold that picks between pre-audited program
arms, and the comparison that uses it, in one jax-free place.

``gates.THRESHOLDS`` is the single assignment of each constant
(``partition``'s 4 KB/row, ``hist_reduce``'s 256 KB wide-shape gate,
``deep_layout``'s leaf/record caps, ``leafwise_layout``'s run slots,
serve's sharded-dispatch work floor, hist backend "auto",
``Params.predict_layout="auto"``, the resilience chunk-cap ladder);
``gates.resolve`` is the one entry the engine, serve and resilience call
sites route through, and it records each choice (``decisions()``, the
``dryad_policy_choice{gate,arm}`` gauge, serve's ``/stats``).  Nothing
overrides the dict at run time: a gate moves by an edit to it, argued
from a chip run.

The hard invariant, machine-pinned by ``analysis --ci``: a gate never
changes traced-program semantics — only which PRE-AUDITED arm
dispatches.  The package is jax-free by lint (``policy-jax-free``,
transitive): gate resolution must work in the fleet control plane while
a device is wedged.  The one sanctioned jax boundary is lazy and outside
the resolution path: ``device.current_device_kind`` (waived probe).
"""

from dryad_tpu.policy.device import current_device_kind
from dryad_tpu.policy.gates import (
    GATE_NAMES,
    THRESHOLDS,
    decisions,
    gate_value,
    resolve,
    stats_block,
)

__all__ = [
    "GATE_NAMES",
    "THRESHOLDS",
    "current_device_kind",
    "decisions",
    "gate_value",
    "resolve",
    "stats_block",
]
