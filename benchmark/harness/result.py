"""The last line of a run, and the lines that show what was compared."""

from __future__ import annotations

import json
import sys


def judge(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """``numbers`` compared with ``limits``: a number passes where it is at
    most its limit (an exact comparison has the limit 0).  A number with no
    limit, a limit with no number, or a number that is not finite, fails."""
    compared = {}
    ok = bool(limits)
    for name, limit in limits.items():
        value = numbers.get(name)
        good = value is not None and value == value and value <= limit
        compared[name] = {"value": value, "limit": limit}
        ok = ok and good
    return ok, compared


def emit(correct: bool, attempted: int, failed: int, metrics: dict, device: dict,
         compared: dict, breakdown: dict | None = None) -> None:
    """Each number compared beside its limit on standard error, then the
    result as the last line of standard output, ``compared`` last in it."""
    for name, rec in compared.items():
        print(f"compared {name}: value {rec['value']!r} limit {rec['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    line = {"correct": bool(correct), "attempted": int(attempted), "failed": int(failed),
            "metrics": metrics, "device": device}
    if breakdown:
        line["breakdown"] = breakdown
    line["compared"] = {k: [v["value"], v["limit"]] for k, v in compared.items()}
    print(json.dumps(line), flush=True)
