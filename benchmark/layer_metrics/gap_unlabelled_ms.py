"""Idle time between programs that no ``train.*`` annotation covers, per
turn of the loop, on the trace's own clock from the first program's start to
the last one's end (``benchmark/harness/scopes.py``)."""

from benchmark.harness import scopes


def read(facts):
    r = scopes.read()
    if not r or not r["annotated"] or not facts.get("window_chunks"):
        return None
    return 1000.0 * r["gap_s"].get(scopes.UNLABELLED, 0.0) / facts["window_chunks"]
