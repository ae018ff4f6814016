"""The ``nbody.snapshot`` cell at sizes a test run holds: ``correct``
holds for a sound run and fails for the control and for faults planted
in the timed path; a traced run carries the runtime's per-layer
metrics; and the readers' arithmetic on hand-made runs.

Each run goes through the harness as the benchmark does; only the look
for a chip is stood in for, and the configuration is shrunk as it is
read.
"""
import itertools
import sys
import types

import pytest

import run
from peaks import Peaks

WORKLOAD = "nbody.snapshot"
TINY = {"bodies": 64, "block_size": 16, "nprocs": 4}  # 4x4 blocks
SECONDS = 0.5
SEED = 2**31 + 11  # above 32 signed bits: run seeds may be that large
LAYER_METRICS = ("matmul_roofline.nbody", "combine_ms.nbody",
                 "combines_per_sweep.nbody")


@pytest.fixture
def tiny(monkeypatch):
    """The harness on the CPU, with the cell's configuration shrunk."""
    resolve = run.resolve

    def tiny_resolve(*args, **kwargs):
        cell, e2e, layer = resolve(*args, **kwargs)
        cell.config = {**cell.config, **TINY}
        return cell, e2e, layer

    monkeypatch.setattr(run, "resolve", tiny_resolve)
    monkeypatch.setattr(run, "device_info", lambda chips: {
        "platform": "cpu", "kind": "cpu", "count": 1})
    monkeypatch.setattr(run, "peaks_for", lambda kind: None)


def run_tiny(trace=False):
    return run.run_cell(WORKLOAD, SEED, SECONDS, trace)


def test_sound_run_is_correct(tiny):
    result, outcome = run_tiny()
    assert result["correct"], result["checks"]
    assert set(result["checks"]) == {"velocity_max_rel_err",
                                     "position_max_abs_err",
                                     "energy_max_rel_err"}
    assert result["attempted"] >= 4 and result["failed"] == 0
    assert result["attempted"] % 4 == 0  # whole chunks
    assert set(result["metrics"]) == {"sweep_ms", "setup_s"}
    assert outcome.info["compiles_in_window"] == 0
    assert list(result)[-1] == "checks"


def test_traced_run_reports_its_layers(tiny):
    """A ``--trace 1`` run carries the runtime's combine time and count;
    on the CPU the device's roofline finds nothing to read."""
    result, outcome = run_tiny(trace=True)
    assert result["correct"], result["checks"]
    got = result["metrics"]
    assert {"combine_ms.nbody", "combines_per_sweep.nbody"} <= set(got)
    assert "matmul_roofline.nbody" not in got
    assert got["combine_ms.nbody"]["value"] > 0
    # per step at 4x4 blocks: two row sums of 16 partials each, and the
    # energy's 4 partials once a chunk of 4 steps
    assert got["combines_per_sweep.nbody"]["value"] == pytest.approx(33.0)
    assert {"busy_s", "window_s"} <= set(result["device"])


def test_control_fails_the_limits(tiny):
    """The text on plain bfloat16 NumPy arrays, put in the runtime's
    place, must come out as not correct by the harness's own verdict."""
    program = run.load_module(f"{run.BENCH}/programs/nbody.py", "ctl")
    with program.control():
        result, _ = run_tiny()
    assert result["correct"] is False, result["checks"]


def _bfloat16_operands(monkeypatch):
    """The outer products from operands rounded to bfloat16's 8-bit
    significand: what a float32 dot that takes one bfloat16 pass
    computes.  ``reduce_precision`` is kept where XLA's excess precision
    would drop a round trip through ``astype``."""
    from jax import lax

    from repro.exec import backend

    build = backend._repro_matmul

    def rounded(jnp, *args):
        prog = build(jnp, *args)

        def repro_matmul(x, y):
            return prog(lax.reduce_precision(x, 8, 7),
                        lax.reduce_precision(y, 8, 7))

        return repro_matmul

    monkeypatch.setattr(backend, "_repro_matmul", rounded)


def _one_combine_left_out(monkeypatch):
    """One combine of a partial sum, inside the first chunk of the
    window (a chunk runs 132 combines at 4x4 blocks), never lands."""
    from repro.exec.backend import JaxBackend

    stage = JaxBackend._stage_combine
    seen = itertools.count()

    def dropped(self, p, moved):
        key, launch = stage(self, p, moved)
        return (key, (lambda: None)) if next(seen) == 200 else (key, launch)

    monkeypatch.setattr(JaxBackend, "_stage_combine", dropped)


@pytest.mark.parametrize("fault", [_bfloat16_operands, _one_combine_left_out],
                         ids=["bfloat16_operands", "one_combine_left_out"])
def test_fault_in_the_timed_path_fails(fault, tiny, monkeypatch):
    fault(monkeypatch)
    result, _ = run_tiny()
    assert not result["correct"], result["checks"]


def reader(name):
    return run.load_module(f"{run.BENCH}/metrics/{name}.py", f"m_{name}")


V5E = Peaks(flops_bf16=197e12, hbm_bytes_per_s=819e9, hbm_bytes=16e9)


def test_roofline_reader_arithmetic():
    """1024 bodies in float32: a step's four outer products move at
    least 4 x (1024^2 + 2 x 1024) x 4 B; at 819 GB/s over 5 ms of
    ``jit_repro_matmul`` per step that is 0.4104%."""
    mod = reader("matmul_roofline.nbody")
    assert mod.least_bytes(1024, 4) == 4 * (1024 * 1024 + 2048) * 4
    run_ = {"counters": {"sweeps": 4, "bodies": 1024, "itemsize": 4},
            "trace": {"device_ops": [["jit_repro_update", 1.0],
                                     ["jit_repro_matmul", 0.02]]},
            "peaks": V5E}
    want = 100.0 * (4 * (1024 * 1024 + 2048) * 4 / 819e9) / (0.02 / 4)
    assert mod.read(run_) == pytest.approx(want)
    assert mod.read(run_) == pytest.approx(0.41050, rel=1e-4)


@pytest.mark.parametrize("change", [
    {"trace": None}, {"peaks": None},
    {"counters": {"sweeps": 0, "bodies": 1024, "itemsize": 4}},
    {"trace": {"device_ops": [["jit_repro_update", 1.0]]}},
], ids=["no_trace", "no_peaks", "no_sweep", "no_matmul_program"])
def test_roofline_reader_finds_nothing(change):
    run_ = {"counters": {"sweeps": 4, "bodies": 1024, "itemsize": 4},
            "trace": {"device_ops": [["jit_repro_matmul", 0.02]]},
            "peaks": V5E, **change}
    assert reader("matmul_roofline.nbody").read(run_) is None


# (metric, the profiler session's totals it reads, its value over 4 steps)
SESSION_CASES = [
    ("combine_ms.nbody", {"spans": {"exec.combine": (264, 0.6)}}, 150.0),
    ("combines_per_sweep.nbody", {"counters": {"n_combine": 132}}, 33.0),
]


@pytest.mark.parametrize("name,totals,want", SESSION_CASES,
                         ids=[c[0] for c in SESSION_CASES])
def test_session_reader_arithmetic(name, totals, want, monkeypatch):
    obs = types.SimpleNamespace(profile_totals=lambda: totals)
    monkeypatch.setitem(sys.modules, "repro.obs", obs)
    read = reader(name).read
    assert read({"counters": {"sweeps": 4}}) == pytest.approx(want)
    assert read({"counters": {"sweeps": 0}}) is None
    obs.profile_totals = lambda: {"spans": {}, "counters": {}}
    assert read({"counters": {"sweeps": 4}}) is None  # the parent's runtime
    monkeypatch.delitem(sys.modules, "repro.obs")
    assert read({"counters": {"sweeps": 4}}) is None


@pytest.mark.parametrize("name", LAYER_METRICS)
def test_metric_is_declared_for_the_cell(name):
    (entry,) = [m for m in run.load_spec()["per_layer"] if m["name"] == name]
    assert entry["workloads"] == [WORKLOAD]
    assert entry["moves"] == "sweep_ms"
