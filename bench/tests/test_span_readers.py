"""The readers of the runtime's own spans and counters: their arithmetic
on what the runtime summed over the latest profiler session, and None
where it summed nothing for them (a runtime without those sums, the
control, a window with no sweep)."""
import sys
import types

import pytest

import run

# (metric, the session's totals it reads, its value over 4 sweeps)
CASES = [
    ("insert_ms.jacobi", {"spans": {"record.insert": (9, 0.2)}}, 50.0),
    ("scan_steps_per_sweep.jacobi", {"counters": {"scan_steps": 1000}},
     250.0),
    ("plan_ms.jacobi", {"spans": {"plan": (3, 0.04)}}, 10.0),
    ("stage_ms.jacobi", {"spans": {"exec.stage": (80, 8.0)}}, 2000.0),
    ("readback_ms.jacobi", {"spans": {"exec.readback": (80, 2.0)}}, 500.0),
    ("host_device_mb_per_sweep.jacobi",
     {"counters": {"h2d_bytes": 6_000_000, "d2h_bytes": 2_000_000}}, 2.0),
]
IDS = [c[0] for c in CASES]


def reader(name):
    return run.load_module(f"{run.BENCH}/metrics/{name}.py", f"m_{name}")


@pytest.fixture
def session(monkeypatch):
    """Stand in for the runtime's ``repro.obs`` with the given totals."""

    def install(totals):
        obs = types.SimpleNamespace(profile_totals=lambda: totals)
        monkeypatch.setitem(sys.modules, "repro.obs", obs)

    return install


@pytest.mark.parametrize("name,totals,want", CASES, ids=IDS)
def test_reader_arithmetic(name, totals, want, session):
    session(totals)
    got = reader(name).read({"counters": {"sweeps": 4}})
    assert got == pytest.approx(want)


@pytest.mark.parametrize("name,totals,want", CASES, ids=IDS)
def test_reader_finds_nothing_without_its_key(name, totals, want, session):
    read = reader(name).read
    session({})  # no profiler session ran a stage
    assert read({"counters": {"sweeps": 4}}) is None
    session({"spans": {"other": (1, 1.0)}, "counters": {"other": 1}})
    assert read({"counters": {"sweeps": 4}}) is None
    session(totals)
    assert read({"counters": {"sweeps": 0}}) is None
    assert read({"counters": {}}) is None


@pytest.mark.parametrize("name,totals,want", CASES, ids=IDS)
def test_reader_finds_nothing_in_a_runtime_without_the_sums(
        name, totals, want, monkeypatch):
    monkeypatch.setitem(sys.modules, "repro.obs", types.ModuleType("obs"))
    assert reader(name).read({"counters": {"sweeps": 4}}) is None
    monkeypatch.delitem(sys.modules, "repro.obs")
    assert reader(name).read({"counters": {"sweeps": 4}}) is None


def test_host_device_bytes_with_one_direction(session):
    session({"counters": {"h2d_bytes": 4_000_000}})
    read = reader("host_device_mb_per_sweep.jacobi").read
    assert read({"counters": {"sweeps": 4}}) == pytest.approx(1.0)


@pytest.mark.parametrize("name,totals,want", CASES, ids=IDS)
def test_metric_is_declared_for_both_cells(name, totals, want):
    (entry,) = [m for m in run.load_spec()["per_layer"]
                if m["name"] == name]
    assert entry["workloads"] == ["jacobi.batch", "jacobi.converge"]
    assert entry["moves"] == "sweep_ms"
