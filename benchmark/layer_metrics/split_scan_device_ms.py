"""Device time an iteration of candidate ranking, node tables and the split
scan: leaf operations in the scope ``dryad.split_scan``
(``benchmark/harness/scopes.py``)."""

from benchmark.harness import scopes


def read(facts):
    return scopes.device_ms_per_iter(facts, "dryad.split_scan")
