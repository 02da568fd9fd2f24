"""Wall of the program's span ``data.sketch``, whole run: the quantile sketch
(and a CSR table's bundle plan) of the train table; a bound table has none."""

from benchmark.harness import setup_series


def read(facts):
    return setup_series.span_seconds("data.sketch")
