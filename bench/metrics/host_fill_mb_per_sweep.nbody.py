"""Bytes the front end's fills wrote on the host per step: the
runtime's ``fill_host_bytes`` counter in the traced window, in MB
(1e6 B).

The runtime counts what it does while a profiler records
(``repro.obs.profile_totals()``), and the traced window is the
profiler's session.  A count of zero bytes leaves no counter, so a
window in which the device made blocks from fill records
(``n_fill_device``) and no fill wrote on the host reads 0; a runtime
with neither count gives nothing.
"""
import sys


def read(run):
    obs = sys.modules.get("repro.obs")
    counters = getattr(obs, "profile_totals", dict)().get("counters", {})
    sweeps = run["counters"].get("sweeps")
    if not sweeps or not ({"fill_host_bytes", "n_fill_device"} & set(counters)):
        return None
    return counters.get("fill_host_bytes", 0) / 1e6 / sweeps
