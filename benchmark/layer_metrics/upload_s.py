"""Wall of the program's span ``train.setup/upload``, whole run: the host's
wall of placing the train and valid tables and the score column on the device
or devices.  It waits for no transfer (the span adds no sync): what the
transfers still owe is paid in the first chunk's wait."""

from benchmark.harness import setup_series


def read(facts):
    return setup_series.span_seconds("train.setup/upload")
