"""Window rules shared by every program of the benchmark.

Each end-to-end metric is computed here, from the times and counts that
a program records, so that every program and every later PR reads a
window the same way.
"""
from __future__ import annotations

import time
from typing import Callable, Optional


def run_window(seconds: float, chunk: Callable[[], int],
               finish: Optional[Callable[[], None]] = None,
               clock: Callable[[], float] = time.perf_counter):
    """Run ``chunk`` until ``seconds`` have passed since the window
    opened, then ``finish``, and return ``(units, t0, t_end)``.

    The window closes at the end of the chunk that crosses the deadline:
    the chunk in flight is finished and counted, never cut.  ``chunk``
    returns how many units (sweeps) it completed; ``finish`` completes
    whatever they left pending, inside the window."""
    t0 = clock()
    units = 0
    while clock() - t0 < seconds:
        units += chunk()
    if finish is not None:
        finish()
    return units, t0, clock()


def sweep_ms(units: int, t0: float, t_end: float) -> float:
    """The whole window over all sweeps completed in it, in ms."""
    if units < 1:
        raise ValueError("no sweep completed in the window")
    return 1e3 * (t_end - t0) / units
