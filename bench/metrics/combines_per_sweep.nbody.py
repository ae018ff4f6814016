"""Combines of partial reductions the execute layer ran per step: its
``n_combine`` counter in the traced window (the axis sums of the forces
combined across block columns, and the energy's sum).

The runtime counts what it does while a profiler records
(``repro.obs.profile_totals()``), and the traced window is the
profiler's session; a runtime without that count gives nothing.
"""
import sys


def read(run):
    obs = sys.modules.get("repro.obs")
    counters = getattr(obs, "profile_totals", dict)().get("counters", {})
    sweeps = run["counters"].get("sweeps")
    if not sweeps or "n_combine" not in counters:
        return None
    return counters["n_combine"] / sweeps
