"""The histogram kernels' share of their roofline: the same algorithmic
bytes and additions of an iteration's histogram passes over the device time
of the kernels' events, per iteration.  It counts the work, not the one-hot
products, so it reads the same whatever implements the pass."""

from benchmark.counts import gbdt_iteration as counts

from benchmark.layer_metrics.hist_time_share import KERNELS  # noqa: F401


def read(facts):
    t = facts["trace"]
    if not t or not t["kernel_s"].get("hist") or not facts["window_iters"] or not facts["peaks"]:
        return None
    work = counts.of_shape(facts["shape"])
    return counts.share(work, facts["peaks"], t["kernel_s"]["hist"] / facts["window_iters"])
