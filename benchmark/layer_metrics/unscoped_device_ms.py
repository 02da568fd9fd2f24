"""Device time an iteration that the scopes cannot see: leaf operations in no
``dryad.*`` scope and matched by no kernel needle.  ``scopes.py`` prints the
ten largest of them by name."""

from benchmark.harness import scopes


def read(facts):
    return scopes.device_ms_per_iter(facts, scopes.UNSCOPED)
