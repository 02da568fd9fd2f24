"""Mean wall of the program's span ``train.fetch.checkpoint/materialize`` in
the window: fetching the tree tables, with the programs that compiles."""

from benchmark.harness import scopes


def read(facts):
    return scopes.span_mean_ms(facts, "train.fetch.checkpoint/materialize")
