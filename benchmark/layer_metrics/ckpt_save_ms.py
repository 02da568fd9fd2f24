"""Mean wall of the program's span ``train.fetch.checkpoint/save`` in the
window: writing the checkpoint to disk."""

from benchmark.harness import scopes


def read(facts):
    return scopes.span_mean_ms(facts, "train.fetch.checkpoint/save")
