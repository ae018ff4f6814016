"""Worker time combining partial reductions per step: the seconds of
the runtime's ``repro.exec.combine`` spans (a combine's staging and its
launch, each nested in the stage span that holds it) in the traced
window, summed over the worker threads, in ms.

The runtime sums the spans it puts in a profiler's trace
(``repro.obs.profile_totals()``), and the traced window is the
profiler's session; a runtime without that sum gives nothing.
"""
import sys


def read(run):
    obs = sys.modules.get("repro.obs")
    spans = getattr(obs, "profile_totals", dict)().get("spans", {})
    sweeps = run["counters"].get("sweeps")
    if not sweeps or "exec.combine" not in spans:
        return None
    return 1e3 * spans["exec.combine"][1] / sweeps
