"""Device time an iteration of the missing-value work: leaf operations in
the scope ``dryad.missing`` (``benchmark/harness/scopes.py``): the split
scan's missing-right plane, its stacked argmax and the left sums' correction,
and route's look-up of the learned direction.  None where the trace or the
program's scopes are missing, and where the program carries no such scope
(one from before it)."""

from benchmark.harness import scopes


def read(facts):
    r = scopes.read()
    if not r or not r["scoped"] or "dryad.missing" not in r["scope_s"]:
        return None
    return scopes.device_ms_per_iter(facts, "dryad.missing")
