"""Device time an iteration around the histogram kernels: leaf operations in
the scope ``dryad.hist`` that the kernels' needles do not match (weights,
tile plans, gathers, untangling the kernel's output, subtraction and the
writes of both children; ``benchmark/harness/scopes.py``)."""

from benchmark.harness import scopes


def read(facts):
    return scopes.device_ms_per_iter(facts, "dryad.hist")
