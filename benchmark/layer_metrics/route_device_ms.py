"""Device time an iteration of the natural-order row partition: leaf
operations in the scope ``dryad.route`` (``benchmark/harness/scopes.py``)."""

from benchmark.harness import scopes


def read(facts):
    return scopes.device_ms_per_iter(facts, "dryad.route")
