"""Device time an iteration of scoring and evaluating the valid sets: leaf
operations in the scope ``dryad.eval`` (``benchmark/harness/scopes.py``)."""

from benchmark.harness import scopes


def read(facts):
    return scopes.device_ms_per_iter(facts, "dryad.eval")
