"""Host time recording a sweep: the benchmark's own span around the
recording loop, over the window, per sweep (ms)."""


def read(run):
    c = run["counters"]
    if not c.get("sweeps") or "record_s" not in c:
        return None
    return 1e3 * c["record_s"] / c["sweeps"]
