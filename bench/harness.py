"""The types a program of the benchmark shares with the harness.

A program (``bench/programs/<name>.py``) exposes ``run(cell, hooks)``:
it builds the system under test from ``cell``, warms it up, runs its
measured window inside ``hooks.window()``, compares what the timed path
produced with its plain reference, and returns an :class:`Outcome`.
"""
from __future__ import annotations

import contextlib
import shutil
import time
from dataclasses import dataclass, field
from typing import Optional

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
# the execute layer's payload paths, as ``Runtime.backend_stats()`` counts them
PAYLOAD_KEYS = ("n_jit", "n_pallas", "n_host", "n_host_untranslated")


class NoAccelerator(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


@dataclass
class Cell:
    """One cell as a program sees it: its configuration and traffic
    (the JSON files' contents), the seed and the window's length."""

    name: str
    config: dict
    traffic: dict
    seed: int
    seconds: float
    chips: int


@dataclass
class Outcome:
    """What a program hands back after its window and its comparison.

    ``e2e``: end-to-end metrics by name.  ``counters``: what the
    per-layer readers read.  ``checks``: ``(name, value, limit)`` of
    every number compared; the run is correct when each value is at or
    under its limit."""

    e2e: dict
    counters: dict
    checks: list
    attempted: int
    failed: int
    memory_peak_bytes: Optional[int] = None
    info: dict = field(default_factory=dict)


class Hooks:
    """What the harness lends a program: host spans on the profiler's
    clock, the measured window, and the device's memory peak."""

    def __init__(self, trace: bool, trace_dir: str):
        self.trace = trace
        self.trace_dir = trace_dir
        self.window_open: Optional[float] = None
        self.compiles_in_window = 0  # backend compilations: should be none
        self._in_window = False

    def _on_event(self, event: str, duration: float, **_) -> None:
        if self._in_window and event == _COMPILE_EVENT:
            self.compiles_in_window += 1

    def span(self, name: str):
        """A host span ``bench.<name>``, on the profiler's clock."""
        import jax

        return jax.profiler.TraceAnnotation(f"bench.{name}")

    @contextlib.contextmanager
    def window(self):
        """The measured window.  Set-up ends where it opens; with tracing
        on, the profiler records exactly this block."""
        import jax

        jax.monitoring.register_event_duration_secs_listener(self._on_event)
        if self.trace:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            shutil.rmtree(self.trace_dir, ignore_errors=True)
            jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
        try:
            self.window_open = time.perf_counter()
            self._in_window = True
            with self.span("window"):
                yield
        finally:
            self._in_window = False
            if self.trace:
                jax.profiler.stop_trace()

    @staticmethod
    def memory_peak_bytes() -> Optional[int]:
        """Peak bytes in use on the fullest chip, where JAX reports it."""
        import jax

        peaks = []
        for d in jax.local_devices():
            stats = d.memory_stats() or {}
            if "peak_bytes_in_use" in stats:
                peaks.append(int(stats["peak_bytes_in_use"]))
        return max(peaks) if peaks else None


def payload_delta(before: dict, after: dict) -> dict:
    """Payloads per path between two ``Runtime.backend_stats()`` reads."""
    return {k: int(after.get(k, 0)) - int(before.get(k, 0))
            for k in PAYLOAD_KEYS}
