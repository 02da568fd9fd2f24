"""The device as jax reports it, the table of peaks, and what it holds."""

from __future__ import annotations

import json
import os

from benchmark.harness.manifest import BENCH


class NoChip(RuntimeError):
    """jax found no accelerator, or fewer chips than the cell asks for."""


def check(chips: int, rehearse_cpu: bool):
    """The devices to run on.  Raises NoChip unless jax sees at least
    ``chips`` TPU devices; a rehearsal takes the CPU instead and is marked
    as such in everything it prints."""
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if rehearse_cpu:
        if platform != "cpu":
            raise NoChip(f"--rehearse-cpu wants a CPU-only jax, found {platform!r}")
        return devices[:1]
    if platform != "tpu":
        raise NoChip(f"jax initialised with platform {platform!r} "
                     f"({devices[0].device_kind}), not 'tpu'")
    if len(devices) < chips:
        raise NoChip(f"the cell asks for {chips} chips, jax sees {len(devices)}")
    return devices[:chips]


def peaks(device_kind: str) -> dict:
    with open(os.path.join(BENCH, "peaks.json"), encoding="utf-8") as f:
        table = json.load(f)
    if device_kind not in table or device_kind.startswith("_"):
        raise KeyError(f"device kind {device_kind!r} is not in benchmark/peaks.json")
    return table[device_kind]


def live_peak_bytes(devices) -> int:
    """The allocator's own peak on the fullest chip: live buffers only.  It
    does not hold a compiled program's temporaries (PERF.md section 6)."""
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


def bytes_limit(devices) -> int:
    return int((devices[0].memory_stats() or {}).get("bytes_limit", 0))


def describe(devices, memory_peak_bytes: int) -> dict:
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": int(memory_peak_bytes)}
