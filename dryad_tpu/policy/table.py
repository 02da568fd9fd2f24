"""The device-keyed calibration table (pure stdlib: json/os/warnings).

Layout of ``goldens/calibration.json`` (schema-versioned, round-tripped
by ``save_table``/``load_table``)::

    {"calibration_schema": 1,
     "devices": {
       "_default": {"gates": { <gate>: { <key>: value, ... }, ... }},
       "TPU v5e":  {"gates": {...}, "git_rev": "...", "measured": {...}}
     }}

Resolution overlays, most specific last: the CODE defaults below (the
pre-policy hand-tuned constants — the ultimate fallback when the file
itself is unreadable), then the table's ``"_default"`` entry, then the
entry for the caller's ``device_kind``.  A device_kind with no entry is
the NORMAL state for the committed table (it ships only ``"_default"``)
and resolves silently to the defaults; loud-once fallback (one
``warnings.warn`` per process, surfaced in ``gates.stats_block``) is
reserved for genuinely broken states: an unreadable/corrupt/
wrong-schema table file, or an unknown device key in an EXPLICITLY
loaded table (``DRYAD_POLICY_TABLE`` / ``load_table(path)``), where the
operator clearly expected calibrated entries to apply.

The committed ``_default`` gates MUST stay equal to ``GATE_DEFAULTS``
(``calibrate.run_selftest`` and tests/test_policy.py pin it): the
parity contract is that the default table resolves bitwise-identically
to the pre-PR hardcoded constants.
"""

from __future__ import annotations

import copy
import json
import os
import warnings
from typing import Optional

SCHEMA_VERSION = 1
DEFAULT_DEVICE_KEY = "_default"
GOLDEN_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "goldens", "calibration.json")
#: explicit table override for a whole process (tests, operators)
TABLE_ENV = "DRYAD_POLICY_TABLE"

#: The pre-policy hand-tuned constants, verbatim (module:line cites the
#: pre-r23 home).  These are CODE, not config: the committed golden's
#: ``_default`` entry must equal this dict byte-for-byte on load.
GATE_DEFAULTS: dict = {
    # levelwise.partition_prefers_reduce (r5): masked reduce over the
    # contiguous (N, F) matrix while F*itemsize <= 4 KB/row, else gather
    "partition": {"reduce_max_row_bytes": 4096},
    # config.HIST_REDUCE_WIDE_BYTES (r16): feature-parallel reduction
    # once F * B * bin_bytes >= 256 KB AND >1 shard participates
    "hist_reduce": {"wide_bytes": 262144},
    # histogram.resolve_backend "auto": the Pallas kernel on the TPU
    # platform, XLA everywhere else
    "hist_backend": {"pallas_platforms": ["tpu"]},
    # levelwise.deep_layout_supported (r10): calibrated caps — leaf
    # budgets past 512 mandate non-noise empty-segment movement; records
    # past 128 B multiply moved bytes past the recoverable sort+gather
    "deep_layout": {"max_leaves": 512, "max_record_bytes": 128},
    # leafwise_fast._MAX_WIRED_SEGMENTS (r10; 1024 until PR 29): the
    # dense run bookkeeping mandates >= 2*2^D + 2 tiles per level.  4096
    # admits depth cap 12, what unbounded depth maps 255 leaves to; the
    # verdict and its chip numbers are written at that name
    "leafwise_layout": {"max_segments": 4096},
    # predict.stage_trees "auto" (r21): the packed node-word table when
    # every traversal field fits its limb width, legacy otherwise
    "predict_layout": {"preferred": "packed"},
    # predict.SHARDED_MIN_WORK: sharding a predict dispatch pays only
    # past ~32k row-outputs (per-shard blocks vs dispatch cost)
    "predict_sharded": {"min_work": 32768},
    # resilience.RetryPolicy.ch_max_ladder: chunk-cap degradation steps,
    # widest first, ending on the 2-iteration floor
    "chunk_cap": {"ladder": [8, 4, 2]},
}


class CalibrationTable:
    """A loaded table: overlay bookkeeping + the loud-fallback state."""

    def __init__(self, devices: Optional[dict] = None,
                 source: Optional[str] = None, explicit: bool = False,
                 fallback_reason: Optional[str] = None):
        self.devices = devices or {}
        self.source = source
        self.explicit = explicit
        self.fallback_reason = fallback_reason
        self._warned_kinds: set = set()

    def gate_values(self, gate: str, device_kind: Optional[str]) -> dict:
        """The effective key->value dict for one gate: code defaults
        overlaid with ``_default`` then the device entry."""
        out = copy.deepcopy(GATE_DEFAULTS.get(gate, {}))
        for key in (DEFAULT_DEVICE_KEY, device_kind):
            if key is None:
                continue
            entry = self.devices.get(key)
            if entry is None:
                if (key == device_kind and self.explicit
                        and key not in self._warned_kinds):
                    # loud once: the operator loaded a table expecting
                    # this device to be calibrated, and it is not
                    self._warned_kinds.add(key)
                    warnings.warn(
                        f"calibration table {self.source!r} has no entry "
                        f"for device_kind {key!r}; falling back to the "
                        "committed defaults", RuntimeWarning, stacklevel=3)
                continue
            out.update(copy.deepcopy(entry.get("gates", {}).get(gate, {})))
        return out

    def to_dict(self) -> dict:
        return {"calibration_schema": SCHEMA_VERSION,
                "devices": copy.deepcopy(self.devices)}


def load_table(path: Optional[str] = None,
               explicit: Optional[bool] = None) -> CalibrationTable:
    """Load a table file; NEVER raises.  A missing/corrupt/wrong-schema
    file returns an empty table carrying ``fallback_reason`` (the caller
    — ``current_table`` — warns once)."""
    src = path or GOLDEN_PATH
    if explicit is None:
        explicit = path is not None
    try:
        with open(src) as f:
            doc = json.load(f)
    except OSError as e:
        return CalibrationTable(source=src, explicit=explicit,
                                fallback_reason=f"unreadable: {e}")
    except ValueError as e:
        return CalibrationTable(source=src, explicit=explicit,
                                fallback_reason=f"corrupt JSON: {e}")
    if not isinstance(doc, dict) or not isinstance(doc.get("devices"), dict):
        return CalibrationTable(source=src, explicit=explicit,
                                fallback_reason="malformed: no devices map")
    if doc.get("calibration_schema") != SCHEMA_VERSION:
        return CalibrationTable(
            source=src, explicit=explicit,
            fallback_reason=(f"schema {doc.get('calibration_schema')!r} != "
                             f"{SCHEMA_VERSION}"))
    return CalibrationTable(devices=doc["devices"], source=src,
                            explicit=explicit)


def save_table(devices: dict, path: str) -> None:
    """Write a table file ``load_table`` round-trips exactly (sorted keys,
    trailing newline — the committed-goldens diff discipline)."""
    doc = {"calibration_schema": SCHEMA_VERSION, "devices": devices}
    with open(path, "w") as f:
        json.dump(doc, f, indent=1, sort_keys=True)
        f.write("\n")


_current: Optional[CalibrationTable] = None
_warned_fallback = False


def current_table() -> CalibrationTable:
    """The process's table (memoized): ``DRYAD_POLICY_TABLE`` when set,
    else the committed golden.  Warns ONCE per process on a broken file
    (the loud-fallback satellite); resolution proceeds on defaults."""
    global _current, _warned_fallback
    if _current is None:
        env = os.environ.get(TABLE_ENV)
        _current = load_table(env) if env else load_table()
        if _current.fallback_reason and not _warned_fallback:
            _warned_fallback = True
            warnings.warn(
                f"calibration table {_current.source!r} unusable "
                f"({_current.fallback_reason}); every gate resolves on the "
                "committed defaults", RuntimeWarning, stacklevel=2)
    return _current


def reset_cache() -> None:
    """Forget the memoized table AND re-arm the loud-once fallback
    warning (test isolation; also lets an operator re-point
    ``DRYAD_POLICY_TABLE`` mid-process)."""
    global _current, _warned_fallback
    _current = None
    _warned_fallback = False
