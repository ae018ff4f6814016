"""Host time in the runtime's dependency inserts per sweep: the seconds
of its ``repro.record.insert`` spans (recording; the re-insertions of
cone extraction and planning run inside ``repro.plan``) in the traced
window, in ms.

The runtime sums the spans it puts in a profiler's trace
(``repro.obs.profile_totals()``), and the traced window is the
profiler's session; a runtime without that sum gives nothing.
"""
import sys


def read(run):
    obs = sys.modules.get("repro.obs")
    spans = getattr(obs, "profile_totals", dict)().get("spans", {})
    sweeps = run["counters"].get("sweeps")
    if not sweeps or "record.insert" not in spans:
        return None
    return 1e3 * spans["record.insert"][1] / sweeps
