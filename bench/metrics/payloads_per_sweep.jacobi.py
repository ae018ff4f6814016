"""Payloads the execute layer ran per sweep: deltas of
``JaxBackend.stats()`` (jitted, Pallas, host) over the window."""


def read(run):
    c = run["counters"]
    if not c.get("sweeps") or "payloads" not in c:
        return None
    return c["payloads"] / c["sweeps"]
