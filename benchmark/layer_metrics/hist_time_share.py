"""Histogram kernels' device time over device busy time."""

KERNELS = {"hist": ("_hist_tiles", "build_hist_nat")}


def read(facts):
    t = facts["trace"]
    if not t or not t["busy_s"] or not t["kernel_s"].get("hist"):
        return None
    return 100.0 * t["kernel_s"]["hist"] / t["busy_s"]
