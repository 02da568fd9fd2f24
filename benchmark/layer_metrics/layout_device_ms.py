"""Device time an iteration of the leaf-ordered layout's bookkeeping: leaf
operations in the scope ``dryad.layout`` that the permute kernel's needles
do not match (``benchmark/harness/scopes.py``; the kernel itself is
``perm_time_share``)."""

from benchmark.harness import scopes


def read(facts):
    return scopes.device_ms_per_iter(facts, "dryad.layout")
