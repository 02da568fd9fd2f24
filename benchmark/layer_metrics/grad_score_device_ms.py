"""Device time an iteration of the objective's gradients and the score
update: leaf operations in the scopes ``dryad.grad`` and ``dryad.score``
(``benchmark/harness/scopes.py``)."""

from benchmark.harness import scopes


def read(facts):
    return scopes.device_ms_per_iter(facts, "dryad.grad", "dryad.score")
