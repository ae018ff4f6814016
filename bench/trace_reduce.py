"""From a profiler trace to the device numbers of one run.

A JAX profiler trace (``*.xplane.pb``) holds one plane per TPU chip and
one for the host.  This module keeps the few events the benchmark needs
as plain tuples, then reduces them:

* the window: the benchmark's own ``bench.window`` host span;
* device busy time: the union of the intervals in which an operation ran
  on a chip (line ``XLA Ops``), clipped to the window, per chip;
* device time per program name (line ``XLA Modules``);
* idle gaps: the window less the busy union of all chips, each instant
  attributed to what the host was doing, i.e. to the innermost (latest
  started) ``bench.*`` span open at that instant on any thread.

Everything below :func:`load_events` works on tuples, so the tests can
feed it a small recorded trace without the profiler.
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from typing import Iterable, NamedTuple, Optional

SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
NO_SPAN = "(no bench span)"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
_NAME_SUFFIX = re.compile(r"\(\d+\)$")  # "jit_f(123)" -> "jit_f"


class Event(NamedTuple):
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


def find_xplane(trace_dir: str) -> str:
    """The newest ``*.xplane.pb`` under ``trace_dir``."""
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no *.xplane.pb under {trace_dir}")
    return max(paths, key=os.path.getmtime)


def load_events(path: str) -> list:
    """The events the reduction reads: every event on a TPU chip's
    ``XLA Ops`` and ``XLA Modules`` lines, and the host's ``bench.*``
    spans."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    out = []
    for plane in data.planes:
        device = _DEVICE_PLANE.match(plane.name) is not None
        for line in plane.lines:
            if device and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            for ev in line.events:
                if device or ev.name.startswith(SPAN_PREFIX):
                    out.append(Event(plane.name, line.name, ev.name,
                                     float(ev.start_ns),
                                     float(ev.duration_ns)))
    return out


def union(intervals: Iterable) -> list:
    """Merge ``(start, end)`` intervals into sorted disjoint ones."""
    merged: list = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def clip(intervals: Iterable, lo: float, hi: float) -> list:
    out = []
    for s, e in intervals:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            out.append((s, e))
    return out


def total(intervals: Iterable) -> float:
    return sum(e - s for s, e in intervals)


def complement(busy: list, lo: float, hi: float) -> list:
    """The gaps of the window ``[lo, hi)`` that ``busy`` (disjoint,
    sorted) leaves."""
    gaps, t = [], lo
    for s, e in busy:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    return gaps


def attribute(gaps: list, spans: list) -> dict:
    """Seconds of ``gaps`` per host span name.  ``spans`` is a list of
    ``(start, end, name)``; an instant goes to the open span that started
    last, or to :data:`NO_SPAN` where none is open.  Times in ns."""
    points = []  # (time, order, kind, payload): ends before starts
    for g0, g1 in gaps:
        points.append((g0, 1, "gap+", None))
        points.append((g1, 0, "gap-", None))
    for i, (s, e, name) in enumerate(spans):
        if e > s:
            points.append((s, 1, "span+", (s, i, name)))
            points.append((e, 0, "span-", (s, i, name)))
    points.sort(key=lambda p: (p[0], p[1]))
    out: dict = defaultdict(float)
    open_spans: set = set()
    latest: Optional[tuple] = None
    in_gap = 0
    prev_t = None
    for t, _, kind, payload in points:
        if prev_t is not None and in_gap and t > prev_t:
            out[latest[2] if latest else NO_SPAN] += t - prev_t
        prev_t = t
        if kind == "gap+":
            in_gap += 1
        elif kind == "gap-":
            in_gap -= 1
        elif kind == "span+":
            open_spans.add(payload)
            if latest is None or payload[:2] > latest[:2]:
                latest = payload
        else:
            open_spans.discard(payload)
            if payload == latest:
                latest = max(open_spans) if open_spans else None
    return dict(out)


def _program_name(name: str) -> str:
    return _NAME_SUFFIX.sub("", name)


def reduce_events(events: list, top: int = 10) -> dict:
    """Device numbers of one traced window.

    Returns ``window_s`` (the ``bench.window`` span), ``busy_s`` (busy
    union averaged over the chips that ran anything), ``busy_s_by_chip``,
    ``device_ops`` (the ``top`` program names by device seconds, from
    ``XLA Modules``), ``idle_gaps`` (idle seconds by
    host span, largest first, at most ``top``), ``idle_by_span`` (all of
    them) and ``n_chips``."""
    windows = [e for e in events if e.name == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"the trace holds no {WINDOW_SPAN!r} span")
    win = max(windows, key=lambda e: e.dur_ns)
    lo, hi = win.start_ns, win.end_ns

    device = [e for e in events if _DEVICE_PLANE.match(e.plane)]
    chips = sorted({e.plane for e in device})
    busy_by_chip, all_busy = {}, []
    for chip in chips:
        busy = union(clip(((e.start_ns, e.end_ns) for e in device
                           if e.plane == chip and e.line == OPS_LINE),
                          lo, hi))
        busy_by_chip[chip] = total(busy) * 1e-9
        all_busy.extend(busy)

    by_name: dict = defaultdict(float)
    for e in device:
        if e.line == MODULES_LINE:
            s, t = max(e.start_ns, lo), min(e.end_ns, hi)
            if t > s:
                by_name[_program_name(e.name)] += (t - s) * 1e-9

    gaps = complement(union(all_busy), lo, hi)
    spans = [(e.start_ns, e.end_ns, e.name) for e in events
             if e.name.startswith(SPAN_PREFIX) and e.name != WINDOW_SPAN
             and not _DEVICE_PLANE.match(e.plane)]
    idle = {k: v * 1e-9 for k, v in attribute(gaps, spans).items()}

    def ranked(d):
        return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])]

    busy_s = (sum(busy_by_chip.values()) / len(chips)) if chips else 0.0
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": busy_s,
        "busy_s_by_chip": busy_by_chip,
        "n_chips": len(chips),
        "device_ops": ranked(by_name)[:top],
        "idle_gaps": ranked(idle)[:top],
        "idle_by_span": idle,
    }


def reduce_trace(trace_dir: str, top: int = 10) -> dict:
    return reduce_events(load_events(find_xplane(trace_dir)), top=top)
