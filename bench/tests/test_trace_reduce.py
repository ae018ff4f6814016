"""The reduction from trace events to device numbers."""
import json
import os

import pytest

import trace_reduce as tr
from trace_reduce import Event

DATA = os.path.join(os.path.dirname(__file__), "data")
DEV = "/device:TPU:0"
HOST = "/host:CPU"


def small_trace():
    """A 100 ns window: the device runs two programs (one as two ops),
    the host records, then drains, then runs outside any span."""
    return [
        Event(HOST, "python", "bench.window", 0, 100),
        Event(HOST, "python", "bench.record", 0, 30),
        Event(HOST, "python", "bench.drain", 30, 50),
        Event(HOST, "worker", "bench.inner", 40, 10),  # nested, other thread
        Event(DEV, "XLA Modules", "jit_stencil(12)", 10, 20),
        Event(DEV, "XLA Ops", "fusion.1", 10, 5),
        Event(DEV, "XLA Ops", "fusion.2", 12, 8),  # overlaps fusion.1
        Event(DEV, "XLA Modules", "jit_copy(7)", 60, 10),
        Event(DEV, "XLA Ops", "copy.3", 60, 10),
        Event(DEV, "XLA Ops", "late", 95, 20),  # runs past the window
        Event("/device:CPU:0", "XLA Ops", "ignored", 0, 100),
    ]


def test_busy_union_per_name_and_window():
    r = tr.reduce_events(small_trace())
    assert r["window_s"] == pytest.approx(100e-9)
    # ops: [10, 20) union [12, 20) -> 10, [60, 70) -> 10, [95, 100) -> 5
    assert r["busy_s"] == pytest.approx(25e-9)
    assert r["n_chips"] == 1
    assert dict(r["device_ops"]) == pytest.approx(
        {"jit_stencil": 20e-9, "jit_copy": 10e-9})


def test_gaps_go_to_the_innermost_open_span():
    r = tr.reduce_events(small_trace())
    # gaps: [0,10) record, [20,30) record, [30,40) drain, [40,50) inner,
    # [50,60) drain, [70,80) drain, [80,95) no span
    assert r["idle_by_span"] == pytest.approx({
        "bench.record": 20e-9, "bench.drain": 30e-9, "bench.inner": 10e-9,
        tr.NO_SPAN: 15e-9})
    names = [n for n, _ in r["idle_gaps"]]
    assert names[0] == "bench.drain"
    total_idle = sum(r["idle_by_span"].values())
    assert total_idle + r["busy_s"] == pytest.approx(r["window_s"])


def test_union_complement_and_attribute():
    assert tr.union([(5, 7), (1, 3), (2, 4), (7, 8)]) == [(1, 4), (5, 8)]
    assert tr.complement([(1, 4), (5, 8)], 0, 10) == [(0, 1), (4, 5), (8, 10)]
    got = tr.attribute([(0, 10)], [(2, 8, "a"), (4, 6, "b")])
    assert got == {tr.NO_SPAN: 4, "a": 4, "b": 2}


def test_a_trace_without_its_window_is_refused():
    with pytest.raises(ValueError):
        tr.reduce_events([Event(DEV, "XLA Ops", "x", 0, 1)])


def test_a_trace_without_a_device_reads_no_busy_time():
    r = tr.reduce_events([Event(HOST, "python", "bench.window", 0, 10)])
    assert r["n_chips"] == 0 and r["busy_s"] == 0.0
    assert r["idle_by_span"] == {tr.NO_SPAN: pytest.approx(10e-9)}


def test_recorded_tpu_trace():
    """A trace recorded on a TPU v5e by ``record_fixture.py``: two runs of
    one jitted program under ``bench.busy``, a sleep under
    ``bench.sleep``, and an idle tail with no span open."""
    with open(os.path.join(DATA, "tpu_events.json")) as f:
        data = json.load(f)
    assert any(p.startswith("/device:TPU:") for p in data["planes"])
    r = tr.reduce_events([Event(*e) for e in data["events"]])
    assert r["n_chips"] >= 1 and 0 < r["busy_s"] < r["window_s"]
    assert r["device_ops"] and r["device_ops"][0][1] > 0
    idle = r["idle_by_span"]
    assert idle.get("bench.sleep", 0) >= 0.015  # the 20 ms sleep
    assert idle.get(tr.NO_SPAN, 0) >= 0.005  # the 10 ms tail
    assert sum(idle.values()) + r["busy_s"] == pytest.approx(r["window_s"])
