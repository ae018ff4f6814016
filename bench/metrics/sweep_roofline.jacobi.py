"""A sweep's share of its roofline, in %: the least time the chip could
take for a sweep's least bytes (the grid read once, the interior
written once, at the peak HBM rate) over the device's busy time per
sweep.  It reads the same work whatever implements the sweep."""


def least_bytes(grid: int, itemsize: int) -> int:
    return (grid * grid + (grid - 2) * (grid - 2)) * itemsize


def read(run):
    c, trace, peaks = run["counters"], run.get("trace"), run.get("peaks")
    if not trace or not peaks or not c.get("sweeps") or trace["busy_s"] <= 0:
        return None
    least_s = least_bytes(c["grid"], c["itemsize"]) / peaks.hbm_bytes_per_s
    return 100.0 * least_s / (trace["busy_s"] / c["sweeps"])
