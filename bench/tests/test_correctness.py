"""``correct`` holds for sound runs and fails for the control and for
faults planted in the timed path, at sizes a test run holds.

Each run below goes through the harness as the benchmark does: the
cell's program, its window, and its comparison with the plain
reference.  Only the look for a chip is stood in for, and the cell's
configuration is shrunk as it is read.
"""
import numpy as np
import pytest

import run

TINY = {"grid": 34, "block_size": 16, "nprocs": 4}
WORKLOADS = ["jacobi.batch", "jacobi.converge"]
SECONDS = 0.5
SEED = 2**31 + 7  # above 32 signed bits: run seeds may be that large


@pytest.fixture
def tiny(monkeypatch):
    """The harness on the CPU, with every cell's configuration shrunk."""
    resolve = run.resolve

    def tiny_resolve(*args, **kwargs):
        cell, e2e, layer = resolve(*args, **kwargs)
        cell.config = {**cell.config, **TINY}
        return cell, e2e, layer

    monkeypatch.setattr(run, "resolve", tiny_resolve)
    monkeypatch.setattr(run, "device_info", lambda chips: {
        "platform": "cpu", "kind": "cpu", "count": 1})
    monkeypatch.setattr(run, "peaks_for", lambda kind: None)


def run_tiny(workload, trace=False):
    return run.run_cell(workload, SEED, SECONDS, trace)


@pytest.mark.parametrize("workload", WORKLOADS)
def test_sound_run_is_correct(workload, tiny):
    result, outcome = run_tiny(workload)
    assert result["correct"], result["checks"]
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert list(result)[-1] == "checks"
    for name, c in result["checks"].items():
        assert c["value"] <= c["limit"], name


@pytest.mark.parametrize("workload", WORKLOADS)
def test_traced_run_reports_its_layers(workload, tiny):
    """A ``--trace 1`` run carries the cell's per-layer metrics; on the
    CPU the device's (busy, idle, roofline) find nothing to read."""
    result, _ = run_tiny(workload, trace=True)
    _, _, layer = run.resolve(run.load_spec(), workload, SEED, SECONDS)
    host_read = {m["name"] for m in layer if m["source"] != "device_trace"}
    assert result["correct"] and host_read <= set(result["metrics"])
    assert {"busy_s", "window_s"} <= set(result["device"])
    assert set(result["breakdown"]) == {"device_ops", "idle_gaps"}


@pytest.mark.parametrize("workload", WORKLOADS)
def test_control_fails_the_limits(workload, tiny):
    """The reference in bfloat16, put in the program's place, must come
    out as not correct by the harness's own verdict."""
    cell, _, _ = run.resolve(run.load_spec(), workload, SEED, SECONDS)
    program = run.load_module(
        f"{run.BENCH}/programs/{cell.config['program']}.py", "ctl")
    with program.control():
        result, _ = run_tiny(workload)
    assert result["correct"] is False, result["checks"]


def _alter(store):
    def altered(self, p, res):
        res = np.array(res, copy=True)
        res.flat[0] += 0.1
        store(self, p, res)
    return altered


def _unchanged(store):
    def unchanged(self, p, res):
        pass
    return unchanged


def _half(store):
    def half(self, p, res):
        if sum(p.out_frag.block) % 2 == 0:
            store(self, p, res)
    return half


@pytest.mark.parametrize("fault", [_alter, _unchanged, _half],
                         ids=["answer_altered", "state_unchanged",
                              "half_left_out"])
@pytest.mark.parametrize("workload", WORKLOADS)
def test_fault_in_the_timed_path_fails(workload, fault, tiny, monkeypatch):
    from repro.exec.backend import JaxBackend

    monkeypatch.setattr(JaxBackend, "_store", fault(JaxBackend._store))
    result, _ = run_tiny(workload)
    assert not result["correct"], result["checks"]


def test_the_harness_refuses_the_cpu():
    with pytest.raises(run.NoAccelerator):
        run.device_info(1)
