"""Wall of the program's span ``data.bin``, whole run: binning the train table
and every table bound to its mapper."""

from benchmark.harness import setup_series


def read(facts):
    return setup_series.span_seconds("data.bin")
