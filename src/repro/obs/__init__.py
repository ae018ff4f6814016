"""repro.obs — runtime tracing, Perfetto export, and wait attribution.

The observability layer of the record → plan → execute → demand
pipeline.  Three pieces:

* :class:`TraceCollector` (:mod:`repro.obs.collector`) — a lock-free
  ring buffer of structured lifecycle events (op recorded / planned /
  enqueued / executed, message posted / progressed / delivered, worker
  wait spans tagged with *why*), installed globally via
  :func:`repro.trace`, ``ExecutionPolicy(trace=True)`` or
  ``REPRO_TRACE=1``.  Disabled tracing is a true no-op.  The
  runtime's stage spans (:func:`span`) run under a collector or while a
  JAX profiler records; under a profiler they are ``jax.profiler``
  annotations named ``repro.*``, on the device trace's clock, and
  :func:`profile_totals` sums them, and the bytes and dependency scans
  counted meanwhile, for the latest profiler session.
* :func:`export_trace` (:mod:`repro.obs.export`) — Chrome-trace /
  Perfetto JSON: one track per worker and per channel, flow arrows from
  each message's delivery to the compute op it unblocked, counter
  tracks for queue depths and in-flight messages.
* :func:`attribution` (:mod:`repro.obs.attribution`) — charges every
  wait span back to the op/message that ended it and reports the top-K
  wait sources, turning the paper's aggregate wait% into named causes.

Quick use::

    import repro

    with repro.trace("run_trace.json") as tr:
        with repro.runtime(flush="async", nprocs=8):
            ... numpy program ...
    print(repro.attribution(tr).format(k=5))
"""
from .attribution import AttributionReport, WaitSpan, attribution
from .collector import (
    CURRENT,
    DEFAULT_CAPACITY,
    TraceCollector,
    activate,
    current_tracer,
    deactivate,
    profile_totals,
    span,
    trace,
)
from .export import export_trace, validate_trace

__all__ = [
    "TraceCollector",
    "trace",
    "activate",
    "deactivate",
    "current_tracer",
    "span",
    "profile_totals",
    "DEFAULT_CAPACITY",
    "export_trace",
    "validate_trace",
    "attribution",
    "AttributionReport",
    "WaitSpan",
]
