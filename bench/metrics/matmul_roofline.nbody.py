"""The outer products' share of their roofline, in %: the least time
the chip could take for a step's four rank-1 outer products over the
device seconds of the ``jit_repro_matmul`` programs per step.  With an
inner dimension of 1 a product is bound by memory, so its least time is
its least bytes (two ``(n, 1)`` operands read, the ``n`` x ``n`` result
written) at the peak HBM rate."""

OUTER_PRODUCTS = 4  # two per coordinate: a[i] and a[j] over the pairs
PROGRAM = "jit_repro_matmul"


def least_bytes(bodies: int, itemsize: int) -> int:
    """A step's outer products: ``4 x (n^2 + 2n)`` elements."""
    return OUTER_PRODUCTS * (bodies * bodies + 2 * bodies) * itemsize


def read(run):
    c, trace, peaks = run["counters"], run.get("trace"), run.get("peaks")
    if not trace or not peaks or not c.get("sweeps"):
        return None
    seconds = dict(trace.get("device_ops", ())).get(PROGRAM, 0.0)
    if seconds <= 0:
        return None
    least_s = least_bytes(c["bodies"], c["itemsize"]) / peaks.hbm_bytes_per_s
    return 100.0 * least_s / (seconds / c["sweeps"])
