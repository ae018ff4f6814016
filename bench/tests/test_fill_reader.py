"""The reader of the front end's host fill bytes: MB per step from the
runtime's ``fill_host_bytes`` counter, 0 where the device made every
filled block from its fill record, and None in a runtime that counts
neither (one older than the fill records, the control); and what a
traced ``nbody.snapshot`` run at sizes a test run holds reads."""
import sys
import types

import pytest

import run

NAME = "host_fill_mb_per_sweep.nbody"


def read(totals, sweeps=4):
    return run.load_module(f"{run.BENCH}/metrics/{NAME}.py",
                           f"m_{NAME}").read({"counters": {"sweeps": sweeps}})


@pytest.fixture
def session(monkeypatch):
    """Stand in for the runtime's ``repro.obs`` with the given totals."""

    def install(totals):
        obs = types.SimpleNamespace(profile_totals=lambda: totals)
        monkeypatch.setitem(sys.modules, "repro.obs", obs)

    return install


@pytest.mark.parametrize("counters,want", [
    ({"fill_host_bytes": 8_000_000}, 2.0),
    ({"fill_host_bytes": 8_000_000, "n_fill_device": 40}, 2.0),
    ({"n_fill_device": 40}, 0.0),
], ids=["eager", "both", "device_only"])
def test_mb_per_step(counters, want, session):
    session({"counters": counters})
    assert read(None) == pytest.approx(want)


@pytest.mark.parametrize("totals", [
    {}, {"spans": {"other": (1, 1.0)}, "counters": {"h2d_bytes": 1}},
], ids=["no_session", "other_counters"])
def test_nothing_without_the_counters(totals, session):
    session(totals)
    assert read(None) is None


def test_nothing_without_a_step(session):
    session({"counters": {"fill_host_bytes": 8_000_000}})
    assert read(None, sweeps=0) is None


def test_nothing_in_a_runtime_without_the_sums(monkeypatch):
    monkeypatch.setitem(sys.modules, "repro.obs", types.ModuleType("obs"))
    assert read(None) is None
    monkeypatch.delitem(sys.modules, "repro.obs")
    assert read(None) is None


def test_metric_is_declared_for_the_nbody_cell():
    (entry,) = [m for m in run.load_spec()["per_layer"] if m["name"] == NAME]
    assert entry == {
        "name": NAME, "unit": "MB", "better": "lower",
        "source": "program_counter", "layer": "front end",
        "moves": "sweep_ms", "workloads": ["nbody.snapshot"]}


def test_a_traced_nbody_run_fills_nothing_on_the_host(monkeypatch):
    """The cell runs on the device backend, so each step's temporaries
    are fill records: the traced line reads 0 MB."""
    resolve = run.resolve

    def tiny_resolve(*args, **kwargs):
        cell, e2e, layer = resolve(*args, **kwargs)
        cell.config = {**cell.config, "bodies": 64, "block_size": 16,
                       "nprocs": 4}
        return cell, e2e, layer

    monkeypatch.setattr(run, "resolve", tiny_resolve)
    monkeypatch.setattr(run, "device_info", lambda chips: {
        "platform": "cpu", "kind": "cpu", "count": 1})
    monkeypatch.setattr(run, "peaks_for", lambda kind: None)
    result, _ = run.run_cell("nbody.snapshot", 2**31 + 17, 0.5, True)
    assert result["correct"], result["checks"]
    assert result["metrics"][NAME]["value"] == 0.0
