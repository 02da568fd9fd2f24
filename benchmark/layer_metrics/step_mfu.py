"""The whole boosting iteration's share of the chip's peak: the least time
the chip needs for the algorithm's histogram work of one iteration
(``benchmark/counts/gbdt_iteration.py``; memory-bound) over the measured
seconds an iteration took in the window, gaps and fetches included."""

from benchmark.counts import gbdt_iteration as counts


def read(facts):
    if not facts["window_iters"] or not facts["peaks"]:
        return None
    work = counts.of_shape(facts["shape"])
    return counts.share(work, facts["peaks"], facts["window_s"] / facts["window_iters"])
