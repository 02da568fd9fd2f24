"""Device time an iteration of the categorical work: leaf operations in the
scope ``dryad.cat`` (``benchmark/harness/scopes.py``): the subset scan's
order and membership row, route's membership look-up, the eval walk's bitset
look-up.  0.0 where the program's scopes are read and it has no such scope
(a numeric model, or a program from before the scope); None where the trace
or its scopes are missing."""

from benchmark.harness import scopes


def read(facts):
    return scopes.device_ms_per_iter(facts, "dryad.cat")
