"""Seconds inside jax's backend-compile event, whole run, for the job's program
families: the program's counter ``dryad_prog_jit_seconds_total``, phase
``backend_compile`` (the cache key, then the cache's read on a hit, or XLA's
compile and the cache's write on a miss: less ``cache_read_s`` it is what the
cache did not save)."""

from benchmark.harness import setup_series


def read(facts):
    return setup_series.jit_seconds("backend_compile")
