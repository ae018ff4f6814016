"""Dependency-list entries the runtime's inserts scanned per sweep: its
``scan_steps`` counter in the traced window (recording, and the
re-insertions of cone extraction and planning).

The runtime counts what it does while a profiler records
(``repro.obs.profile_totals()``), and the traced window is the
profiler's session; a runtime without that count gives nothing.
"""
import sys


def read(run):
    obs = sys.modules.get("repro.obs")
    counters = getattr(obs, "profile_totals", dict)().get("counters", {})
    sweeps = run["counters"].get("sweeps")
    if not sweeps or "scan_steps" not in counters:
        return None
    return counters["scan_steps"] / sweeps
