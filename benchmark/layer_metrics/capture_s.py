"""Wall of every span of the program whose last component is ``capture``,
whole run: a compile boundary's second lowering, ``cost_analysis`` and, under
``DRYAD_PROG_MEMORY=1`` (which the runner sets), its second compile and the
scope map of the HLO text."""

from benchmark.harness import setup_series


def read(facts):
    return setup_series.span_seconds("capture")
