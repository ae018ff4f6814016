"""Stage spans on the profiler's clock and the always-on execute-layer
counters: ``repro.obs.span`` totals under a collector and under a JAX
profiler session, their ``repro.*`` annotations on the host plane of
the profiler's trace, host↔device bytes, the dependency-list scan
total, and the names of jitted payloads."""
import glob

import jax
import numpy as np
import pytest

import repro
from repro import api
from repro.api import ExecutionPolicy, RuntimeConfig
from repro.obs import collector as obs_collector
from repro.obs import trace

# every payload of the program below has a device form, so none runs
# under "exec.host"; "exec.readback" is the read at gather, and the
# reduction's combines run "exec.combine"
SPANS = {"record.insert", "plan", "exec.stage", "exec.launch",
         "exec.readback", "channel.transfer", "exec.combine"}
EXEC_SPANS = ("exec.stage", "exec.launch", "exec.readback", "exec.host")


@pytest.fixture(autouse=True)
def _no_leaked_tracer():
    obs_collector.CURRENT = None
    yield
    obs_collector.CURRENT = None


def _jax_runtime(nprocs=4, block_size=16, **config):
    return repro.runtime(
        RuntimeConfig(nprocs=nprocs, block_size=block_size, **config),
        ExecutionPolicy(flush="async", backend="jax"),
    )


def _program(n=64):
    """Maps, a float64 input, a transfer (roll), a reduction and a
    matmul on the JAX backend, recorded whole and then drained by one
    flush; returns the results, the span totals and the drain's stats."""
    host = np.linspace(0.0, 1.0, n * n, dtype=np.float32).reshape(n, n)
    with _jax_runtime() as rt:
        a = repro.array(host)
        b = a * 2.0 + 1.0
        c = api.roll(b, 1, axis=0) + b
        wide = repro.array(host.astype(np.float64)) * 3.0
        total = np.sum(c)
        mm = a @ b
        rt.flush()
        got = tuple(np.asarray(x) for x in (c, total, wide, mm))
        spans = rt.span_totals()
        stats = rt.stats()
    return got, spans, stats


def test_no_collector_no_span_totals_and_bit_identical():
    plain, spans, _ = _program()
    assert spans == {}
    with trace() as tr:
        traced, traced_spans, _ = _program()
    for x, y in zip(plain, traced):
        np.testing.assert_array_equal(x, y)
    assert set(traced_spans) == SPANS
    assert traced_spans == tr.span_totals()
    for name, (n, seconds) in traced_spans.items():
        assert n > 0 and seconds >= 0.0, name


def test_span_without_collector_is_shared_null_context():
    assert obs_collector.span("plan", 3) is obs_collector.NO_SPAN
    with obs_collector.NO_SPAN:
        pass


def test_span_totals_merge_threads():
    """Each thread keeps its own totals with no lock; reads merge them
    while the threads run, and no count is lost."""
    import sys
    import threading

    col = obs_collector.TraceCollector()
    obs_collector.activate(col)
    n_threads, n_spans = 16, 500

    def work():
        for _ in range(n_spans):
            with obs_collector.span("exec.stage"):
                pass

    prev = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=work, name=f"t{i}")
                   for i in range(n_threads)]
        for t in threads:
            t.start()
        while any(t.is_alive() for t in threads):
            col.span_totals()  # merging while the threads write
        for t in threads:
            t.join(timeout=30)
            assert not t.is_alive()
    finally:
        sys.setswitchinterval(prev)
    with obs_collector.span("plan", flush_id=7):
        pass
    by_thread = col.span_totals_by_thread()
    assert {t: per["exec.stage"][0] for t, per in by_thread.items()
            if "exec.stage" in per} == {f"t{i}": n_spans
                                         for i in range(n_threads)}
    totals = col.span_totals()
    assert totals["exec.stage"][0] == n_threads * n_spans
    assert totals["plan"][0] == 1


def _xplane_events(trace_dir):
    from jax.profiler import ProfileData

    path = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    assert len(path) == 1, path
    data = ProfileData.from_file(path[0])
    out = []
    for plane in data.planes:
        for line in plane.lines:
            for ev in line.events:
                out.append((plane.name, ev.name, float(ev.start_ns),
                            float(ev.duration_ns)))
    return out


def test_spans_land_on_the_profiler_host_plane(tmp_path):
    """Every stage span is a ``repro.*`` event on the host plane of the
    trace, inside the enclosing annotation, and the collector's totals
    agree with the trace's durations (within 5% or 1 ms) and counts.
    The grid is small: few short spans, so that a preemption between
    the two clocks' readings is unlikely to land in one."""
    _program(32)  # compile outside the trace
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation("test.outer"):
            with trace() as tr:
                _program(32)
    finally:
        jax.profiler.stop_trace()
    events = _xplane_events(str(tmp_path))
    (outer,) = [e for e in events if e[1] == "test.outer"]
    lo, hi = outer[2], outer[2] + outer[3]
    spans = [e for e in events if e[1].startswith("repro.")]
    assert {e[0] for e in spans} == {"/host:CPU"}
    assert all(lo <= s and s + d <= hi for _, _, s, d in spans)
    from collections import defaultdict

    seconds, counts = defaultdict(float), defaultdict(int)
    for _, name, _, dur in spans:
        seconds[name[len("repro."):]] += dur * 1e-9
        counts[name[len("repro."):]] += 1
    totals = tr.span_totals()
    assert obs_collector.profile_totals()["spans"] == totals
    assert set(seconds) == set(totals) == SPANS
    for name, (n, s) in totals.items():
        assert counts[name] == n, name
        assert abs(seconds[name] - s) <= max(0.05 * s, 1e-3), (
            name, seconds[name], s)
    # jitted payloads carry their names; no convert program is launched
    names = {e[1] for e in events}
    assert not any("<lambda>" in n or "convert_element_type" in n
                   for n in names), sorted(n for n in names if "Pjit" in n)
    assert any("repro_map_" in n for n in names)
    assert any("repro_matmul" in n for n in names)


def _profiled_program(trace_dir, n=32):
    """:func:`_program` in a profiler session of its own, with no
    collector; returns the trace's events and what the runtime read."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    host = np.linspace(0.0, 1.0, n * n, dtype=np.float32).reshape(n, n)
    jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    try:
        with _jax_runtime() as rt:
            a = repro.array(host)
            b = a * 2.0 + 1.0
            c = api.roll(b, 1, axis=0) + b
            total = np.sum(c)
            mm = a @ b
            rt.flush()
            got = tuple(np.asarray(x) for x in (c, total, mm))
            backend, spans = rt.backend_stats(), rt.span_totals()
    finally:
        jax.profiler.stop_trace()
    return _xplane_events(str(trace_dir)), got, backend, spans


def test_a_profiler_alone_turns_the_spans_on(tmp_path):
    """With no collector, the spans run while a JAX profiler records:
    each lands in its trace, and the session's totals hold them and the
    bytes, dependency scans, matmuls and combines the runtime counted
    meanwhile."""
    _program(32)  # compile, with no profiler recording
    assert obs_collector.span("plan") is obs_collector.NO_SPAN
    events, _, backend, spans = _profiled_program(tmp_path)
    assert spans == {}  # no collector
    session = obs_collector.profile_totals()
    seconds, counts = {}, {}
    for _, name, _, dur in events:
        if name.startswith("repro."):
            key = name[len("repro."):]
            seconds[key] = seconds.get(key, 0.0) + dur * 1e-9
            counts[key] = counts.get(key, 0) + 1
    assert set(session["spans"]) == set(seconds) == SPANS
    for name, (n, s) in session["spans"].items():
        assert counts[name] == n, name
        assert abs(seconds[name] - s) <= max(0.05 * s, 1e-3), name
    assert session["counters"] == {
        k: backend[k] for k in ("h2d_bytes", "d2h_bytes", "scan_steps",
                                "n_matmul", "n_combine")}


def test_each_profiler_session_sums_afresh(tmp_path):
    """A session's totals start at its first span: the same program in
    two sessions, with the runtime run in between, sums the same."""
    _program(32)
    _profiled_program(tmp_path / "one")
    first = obs_collector.profile_totals()
    _program(32)
    _profiled_program(tmp_path / "two")
    second = obs_collector.profile_totals()
    assert second["counters"] == first["counters"]
    assert ({k: n for k, (n, _) in second["spans"].items()}
            == {k: n for k, (n, _) in first["spans"].items()})


def test_exec_spans_bound_worker_compute():
    """Per worker, the ``exec.*`` span seconds cover the worker's
    measured compute time (CPU clock) and fit in its drains' wall time."""
    host = np.linspace(0.0, 1.0, 64 * 64, dtype=np.float32).reshape(64, 64)
    with trace() as tr:
        with _jax_runtime() as rt:
            a = repro.array(host)
            for _ in range(3):
                a = np.sqrt(a * a + 1.0)
                np.asarray(np.sum(a))
            st = rt.stats()
    by_thread = tr.span_totals_by_thread()
    for rank, proc in enumerate(st.procs):
        per = by_thread.get(f"exec-worker-{rank}", {})
        spent = sum(per.get(name, (0, 0.0))[1] for name in EXEC_SPANS)
        assert proc.compute_busy <= spent <= st.elapsed, (rank, per)


def test_host_device_bytes_match_nbytes():
    """64x64 float32 on 16x16 blocks: a block crosses to the device at
    its first touch and back at a read, so ``a * b`` uploads only the 16
    1 KiB blocks of ``b`` (``a + 0.0`` moved ``a``'s), and reading its
    result downloads 16."""
    x = np.arange(64 * 64, dtype=np.float32).reshape(64, 64)
    with _jax_runtime() as rt:
        a, b = repro.array(x), repro.array(x + 1.0)
        np.asarray(a + 0.0)  # builds the backend
        before = rt.backend_stats()
        got = np.asarray(a * b)
        after = rt.backend_stats()
    np.testing.assert_array_equal(got, x * (x + 1.0))
    block = 16 * 16 * 4
    assert after["n_jit"] - before["n_jit"] == 16
    assert after["h2d_bytes"] - before["h2d_bytes"] == 16 * block
    assert after["d2h_bytes"] - before["d2h_bytes"] == 16 * block


def _scan_steps_of_window(sweeps):
    host = np.linspace(0.0, 1.0, 34 * 34, dtype=np.float32).reshape(34, 34)
    with _jax_runtime(fusion=True) as rt:
        grid = repro.array(host)
        c, n, s = grid[1:-1, 1:-1], grid[:-2, 1:-1], grid[2:, 1:-1]
        w, e = grid[1:-1, :-2], grid[1:-1, 2:]
        before = rt.scan_steps
        for _ in range(sweeps):
            c[:] = 0.2 * ((((c + n) + s) + w) + e)
        recorded = rt.scan_steps - before
        np.asarray(grid)
        total = rt.scan_steps - before
        assert rt.backend_stats()["scan_steps"] == rt.scan_steps
    return recorded, total


def test_scan_steps_grow_with_the_lazy_window():
    """Each insert scans its blocks' whole dependency lists, so the
    scans per sweep grow with the number of sweeps left pending."""
    counts = [_scan_steps_of_window(k) for k in (1, 2, 4)]
    recorded = [r for r, _ in counts]
    assert recorded[0] < recorded[1] < recorded[2]
    assert recorded[2] / 4 > recorded[1] / 2 > recorded[0]
    # the flush re-inserts the window (cone extraction, planning) too
    assert all(total > r for r, total in counts)


def test_jitted_payloads_carry_their_names():
    host = np.linspace(0.0, 1.0, 32 * 32, dtype=np.float32).reshape(32, 32)
    with _jax_runtime(nprocs=2) as rt:
        a = repro.array(host)
        np.asarray(np.abs(a - 0.5))
        np.asarray(a @ a)
        cache = dict(rt._exec_backend_obj._programs)
    progs = [v[1] if isinstance(v, tuple) else v for v in cache.values()]
    names = {fn.__name__ for fn in progs if fn is not None}
    assert "repro_map_subtract" in names and "repro_map_absolute" in names
    assert "repro_matmul" in names
    assert all(n.startswith("repro_") for n in names)
    assert len(names) <= len(cache)
