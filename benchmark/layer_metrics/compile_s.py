"""Seconds jax spent tracing, lowering, compiling and fetching programs from
the persistent cache up to the window's open (``jax.monitoring``)."""


def read(facts):
    return facts["compile"]["setup_compile_s"]
