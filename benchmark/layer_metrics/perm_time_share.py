"""Layout movement (the leaf-ordered permute kernel) over device busy time."""

KERNELS = {"perm": ("permute_records",)}


def read(facts):
    t = facts["trace"]
    if not t or not t["busy_s"] or not t["kernel_s"].get("perm"):
        return None
    return 100.0 * t["kernel_s"]["perm"] / t["busy_s"]
