"""Seconds the persistent compile cache took to hand back programs it held,
whole run, for the job's program families: the program's counter
``dryad_prog_jit_seconds_total``, phase ``cache_read`` (jax reports it for a
hit only, and inside that hit's ``backend_compile``)."""

from benchmark.harness import setup_series


def read(facts):
    return setup_series.jit_seconds("cache_read")
