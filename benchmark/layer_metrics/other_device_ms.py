"""Device busy time that is neither histogram nor permute kernel, per
iteration: growers, split scan, partition, gradients, eval."""

from benchmark.layer_metrics import hist_time_share, perm_time_share

KERNELS = {**hist_time_share.KERNELS, **perm_time_share.KERNELS}


def read(facts):
    t = facts["trace"]
    if not t or not t["busy_s"] or not facts["window_iters"]:
        return None
    rest = t["busy_s"] - t["kernel_s"].get("hist", 0.0) - t["kernel_s"].get("perm", 0.0)
    return 1000.0 * rest / facts["window_iters"]
