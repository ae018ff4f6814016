"""Bytes the execute layer moved between host and device per sweep: its
``h2d_bytes`` and ``d2h_bytes`` counters in the traced window, in MB
(1e6 B).

The runtime counts what it does while a profiler records
(``repro.obs.profile_totals()``), and the traced window is the
profiler's session; a runtime without that count gives nothing.
"""
import sys


def read(run):
    obs = sys.modules.get("repro.obs")
    counters = getattr(obs, "profile_totals", dict)().get("counters", {})
    sweeps = run["counters"].get("sweeps")
    moved = [counters[k] for k in ("h2d_bytes", "d2h_bytes") if k in counters]
    if not sweeps or not moved:
        return None
    return sum(moved) / 1e6 / sweeps
