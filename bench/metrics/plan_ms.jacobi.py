"""Host time in the runtime's plan stage per sweep: the seconds of its
``repro.plan`` spans (cone extraction, the plan passes, verification
and the executor submit) in the traced window, in ms.

The runtime sums the spans it puts in a profiler's trace
(``repro.obs.profile_totals()``), and the traced window is the
profiler's session; a runtime without that sum gives nothing.
"""
import sys


def read(run):
    obs = sys.modules.get("repro.obs")
    spans = getattr(obs, "profile_totals", dict)().get("spans", {})
    sweeps = run["counters"].get("sweeps")
    if not sweeps or "plan" not in spans:
        return None
    return 1e3 * spans["plan"][1] / sweeps
