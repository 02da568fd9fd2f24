"""Seconds jit spent tracing to jaxprs and lowering them to MLIR, whole run,
for the job's program families: the program's counter
``dryad_prog_jit_seconds_total``, phases ``trace`` and ``lower``.  No cache
saves these."""

from benchmark.harness import setup_series


def read(facts):
    return setup_series.jit_seconds("trace", "lower")
