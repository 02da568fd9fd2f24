"""The ONE ``device_kind`` derivation (r23 dedupe satellite).

``bench.py``, ``scripts/bench_serve.py`` and the profile CLI all used
to hand-roll ``getattr(dev, "device_kind", None) or dev.platform``
independently; this helper is now the single source, memoized per
process (device topology cannot change mid-run).

It lives in the jax-free policy package because serve's ``/stats``
policy block names the device beside the gate decisions and must load
in the fleet control plane — so the jax probe below is lazy,
best-effort, and the one waived exception to ``policy-jax-free``:
importing this module never pulls jax, and every failure mode (no jax,
no devices, wedged runtime) resolves to ``None``.
"""

from __future__ import annotations

from typing import Optional

_UNRESOLVED = object()
_cached: object = _UNRESOLVED


def current_device_kind() -> Optional[str]:
    """The primary device's kind ("TPU v5e", "cpu", ...), or None when no
    jax runtime is reachable.  Memoized; ``reset()`` un-memoizes (tests)."""
    global _cached
    if _cached is _UNRESOLVED:
        try:
            import jax  # dryadlint: disable=policy-jax-free -- the ONE sanctioned lazy device probe; gate resolution never calls it

            dev = jax.devices()[0]
            _cached = getattr(dev, "device_kind", None) or dev.platform
        except Exception:  # noqa: BLE001 — a stamp probe never raises
            _cached = None
    return _cached  # type: ignore[return-value]


def reset() -> None:
    """Forget the memoized kind (test isolation)."""
    global _cached
    _cached = _UNRESOLVED
