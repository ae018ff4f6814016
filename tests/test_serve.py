"""repro.serve tests: admission control, per-tenant stats isolation,
work-stealing correctness, and the serving lifecycle.

The load-level acceptance gates (>=1.5x concurrent throughput, p99
budget at 200 clients) live in ``benchmarks.serve_load``; these tests
cover the mechanisms at unit scale.
"""
import math
import random
import threading
import time

import numpy as np
import pytest

import repro
from repro.serve import (
    AdmissionController,
    AdmissionError,
    LatencyHistogram,
    Server,
)


# ---------------------------------------------------------------------------
# latency histogram
# ---------------------------------------------------------------------------


def test_histogram_quantiles_and_merge():
    h = LatencyHistogram()
    assert h.count == 0 and h.p99 == 0.0 and h.mean == 0.0
    for ms in range(1, 101):  # 1..100 ms, uniform
        h.record(ms * 1e-3)
    assert h.count == 100
    assert h.max == pytest.approx(0.1)
    # log-spaced buckets: quantiles accurate to the bucket ratio (~12%)
    assert h.p50 == pytest.approx(0.050, rel=0.15)
    assert h.p99 == pytest.approx(0.100, rel=0.15)
    assert h.p50 <= h.p95 <= h.p99 <= h.max
    other = LatencyHistogram()
    other.record(1.0)  # a 1 s outlier
    h.merge(other)
    assert h.count == 101
    assert h.max == pytest.approx(1.0)
    assert h.quantile(1.0) == pytest.approx(1.0)


def test_histogram_clamps_out_of_range():
    h = LatencyHistogram()
    h.record(-1.0)  # negative -> 0
    h.record(float("nan"))
    h.record(1e-9)  # below the grid
    h.record(1e4)  # above the grid: exact max still honest
    assert h.count == 4
    assert h.max == pytest.approx(1e4)
    assert h.quantile(1.0) == pytest.approx(1e4)


def test_histogram_inf_clamps_to_overflow_edge():
    """Regression: one +inf sample used to poison ``max`` — and with it
    every quantile (quantile() clamps its answer to ``max``) and the
    running ``sum``/``mean``, forever."""
    h = LatencyHistogram()
    h.record(float("inf"))
    h.record(5e-3)
    assert h.count == 2
    assert math.isfinite(h.max) and math.isfinite(h.sum)
    assert h.max == pytest.approx(100.0)  # the overflow-bucket edge
    for q in (0.5, 0.99, 1.0):
        assert math.isfinite(h.quantile(q))
    assert math.isfinite(h.mean)
    other = LatencyHistogram()
    other.record(2e-3)
    other.merge(h)  # merging an inf-touched histogram stays finite
    assert math.isfinite(other.max) and math.isfinite(other.p99)


# ---------------------------------------------------------------------------
# admission controller
# ---------------------------------------------------------------------------


def test_admission_queue_full_rejects_immediately():
    adm = AdmissionController(max_inflight=1, max_queue=0)
    adm.admit()
    t0 = time.perf_counter()
    with pytest.raises(AdmissionError) as ei:
        adm.admit()
    assert ei.value.reason == "queue-full"
    assert time.perf_counter() - t0 < 0.5  # shed, not queued
    assert adm.n_admitted == 1 and adm.n_rejected == 1
    adm.release()
    adm.admit()  # slot freed: admissible again
    assert adm.n_admitted == 2


def test_admission_timeout_rejects_queued_request():
    adm = AdmissionController(max_inflight=1, max_queue=4,
                              admission_timeout=0.05)
    adm.admit()
    with pytest.raises(AdmissionError) as ei:
        adm.admit()
    assert ei.value.reason == "timeout"
    assert adm.queued == 0  # the waiter un-queued itself


def test_admission_release_unblocks_queued_waiter():
    adm = AdmissionController(max_inflight=1, max_queue=4)
    adm.admit()
    admitted = threading.Event()

    def waiter():
        adm.admit()
        admitted.set()

    t = threading.Thread(target=waiter)
    t.start()
    time.sleep(0.05)
    assert not admitted.is_set() and adm.queued == 1
    adm.release()
    assert admitted.wait(5.0)
    t.join()
    assert adm.peak_queued == 1 and adm.peak_inflight == 1


def test_admission_close_rejects_queued_and_future():
    adm = AdmissionController(max_inflight=1, max_queue=4)
    adm.admit()
    errors = []

    def waiter():
        try:
            adm.admit()
        except AdmissionError as e:
            errors.append(e.reason)

    t = threading.Thread(target=waiter)
    t.start()
    time.sleep(0.05)
    adm.close()
    t.join(5.0)
    assert errors == ["closed"]
    with pytest.raises(AdmissionError, match="closed"):
        adm.admit()


def test_admission_release_never_lost_with_two_queued_waiters():
    """Regression (lost wakeup): a queued waiter that consumes a
    ``release()`` notify and then sheds itself (deadline passed) used to
    let the notify die with it, stranding the *other* queued waiter even
    though a slot was free.  Race a release against the first waiter's
    deadline, many rounds: the patient (no-deadline) waiter must always
    come through promptly."""
    for round_ in range(15):
        adm = AdmissionController(max_inflight=1, max_queue=4,
                                  admission_timeout=0.03)
        adm.admit()  # slot taken
        results = {}

        def timed():
            try:
                adm.admit()
                results["timed"] = "admitted"
            except AdmissionError as e:
                results["timed"] = e.reason

        def patient():
            try:
                adm.admit()
                results["patient"] = "admitted"
            except AdmissionError as e:
                results["patient"] = e.reason

        ta = threading.Thread(target=timed)
        ta.start()
        time.sleep(0.005)  # "timed" queued first (deadline ~0.03 out)
        adm.admission_timeout = None  # read per-admit(): "patient" waits forever
        tb = threading.Thread(target=patient)
        tb.start()
        time.sleep(0.005)
        # release as close to the timed waiter's deadline as this round
        # lands — across rounds the notify falls on both sides of it
        time.sleep(0.02 + round_ * 0.002)
        adm.release()
        ta.join(5.0)
        assert not ta.is_alive(), (round_, results)
        if results["timed"] == "admitted":
            # the release landed before the deadline (a loaded machine
            # starts the timed waiter late): it took the slot, so the
            # notify was used, not lost; its request finishes now
            adm.release()
        tb.join(10.0)
        assert not tb.is_alive(), (
            f"round {round_}: patient waiter stranded — release notify "
            f"was lost ({results})"
        )
        assert results["patient"] == "admitted", (round_, results)
        assert results["timed"] in ("admitted", "timeout"), (round_, results)
        assert adm.inflight == 1


def test_admission_release_overrelease_clamped_and_counted():
    adm = AdmissionController(max_inflight=2, max_queue=0)
    adm.admit()
    adm.release()
    adm.release()  # over-release: clamped, counted, never negative
    adm.release()
    assert adm.inflight == 0
    assert adm.n_over_released == 2
    # the clamp keeps the window intact: exactly max_inflight admits fit
    adm.admit()
    adm.admit()
    with pytest.raises(AdmissionError, match="queue full"):
        adm.admit()
    assert adm.inflight == 2


def test_admission_stress_window_and_no_starvation():
    """Satellite stress: hammer admit/release from many threads with a
    generous deadline — the in-flight count must never exceed
    ``max_inflight``, no waiter may starve past its deadline, and the
    counters must balance."""
    adm = AdmissionController(max_inflight=4, max_queue=64,
                              admission_timeout=10.0)
    peak_violation = []
    outcomes = []
    lock = threading.Lock()

    def client(seed):
        rng = random.Random(seed)
        for _ in range(25):
            try:
                adm.admit()
            except AdmissionError as e:
                with lock:
                    outcomes.append(e.reason)
                continue
            if adm.inflight > adm.max_inflight:
                with lock:
                    peak_violation.append(adm.inflight)
            time.sleep(rng.random() * 0.002)
            adm.release()
            with lock:
                outcomes.append("ok")

    threads = [threading.Thread(target=client, args=(i,)) for i in range(12)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(60.0)
    assert not any(t.is_alive() for t in threads), "a waiter starved"
    assert not peak_violation, f"window exceeded: {peak_violation}"
    assert outcomes.count("timeout") == 0, "deadline was generous; a timeout means a lost wakeup"
    assert adm.peak_inflight <= adm.max_inflight
    assert adm.inflight == 0 and adm.queued == 0
    assert adm.n_admitted == outcomes.count("ok")


def test_serve_config_validation():
    from repro.api.config import ServeConfig

    cfg = ServeConfig()
    assert cfg.max_inflight == 8 and cfg.max_queue == 64
    assert cfg.replace(max_inflight=2).max_inflight == 2
    with pytest.raises(ValueError, match="max_inflight"):
        ServeConfig(max_inflight=0)
    with pytest.raises(ValueError, match="max_queue"):
        ServeConfig(max_queue=-1)
    with pytest.raises(ValueError, match="admission_timeout"):
        ServeConfig(admission_timeout=0.0)


# ---------------------------------------------------------------------------
# server lifecycle and config surface
# ---------------------------------------------------------------------------


def test_server_requires_async_flush_and_demand_sync():
    from repro.api.config import ExecutionPolicy

    with pytest.raises(ValueError, match="flush='async'"):
        Server(policy=ExecutionPolicy(flush="sim"))
    with pytest.raises(ValueError, match="demand"):
        Server(policy=ExecutionPolicy(flush="async", sync="barrier"))
    with pytest.raises(TypeError, match="unknown server option"):
        Server(bogus_knob=1)


def test_server_rejects_requests_after_close_and_double_close():
    srv = Server(nprocs=2, block_size=8)
    sess = srv.session("t")
    host = np.arange(16.0)

    def fn():
        a = repro.array(host)
        return a + 1.0

    got = sess.request(fn).result()
    np.testing.assert_array_equal(got, host + 1.0)
    srv.close()
    srv.close()  # no-op
    with pytest.raises(AdmissionError, match="closed"):
        sess.request(fn)
    assert sess.stats.n_rejected == 1
    with pytest.raises(AdmissionError, match="closed"):
        srv.session("new-tenant")


def test_request_function_error_releases_admission_slot():
    with Server(nprocs=2, block_size=8, max_inflight=1) as srv:
        sess = srv.session("t")
        with pytest.raises(ValueError, match="boom"):
            sess.request(lambda: (_ for _ in ()).throw(ValueError("boom")))
        assert sess.stats.n_failed == 1
        assert srv.admission.inflight == 0  # permit released
        host = np.arange(16.0)
        got = sess.request(lambda: repro.array(host) * 2.0).result()
        np.testing.assert_array_equal(got, host * 2.0)


def test_request_fn_must_return_arrays():
    with Server(nprocs=2, block_size=8) as srv:
        sess = srv.session("t")
        with pytest.raises(TypeError, match="must return DistArrays"):
            sess.request(lambda: 42)
        assert srv.admission.inflight == 0


# ---------------------------------------------------------------------------
# admission under real load + per-tenant stats isolation
# ---------------------------------------------------------------------------


def test_server_sheds_when_queue_full_under_slow_drain():
    host = np.arange(64.0).reshape(8, 8)
    with Server(nprocs=2, block_size=4, latency=20e-3,
                max_inflight=1, max_queue=0) as srv:
        sess = srv.session("t")

        def fn():
            a = repro.array(host)
            return np.roll(a, 1, axis=0) + a

        r1 = sess.request(fn)  # slow drain (injected wire latency)
        with pytest.raises(AdmissionError) as ei:
            sess.request(fn)
        assert ei.value.reason == "queue-full"
        np.testing.assert_array_equal(
            r1.result(), np.roll(host, 1, axis=0) + host
        )
        assert sess.stats.n_rejected == 1
        assert srv.admission.n_rejected == 1


def test_per_tenant_stats_isolation():
    with Server(nprocs=2, block_size=8) as srv:
        sa, sb = srv.session("a"), srv.session("b")
        ha, hb = np.arange(16.0), np.arange(16.0) * 3.0
        for _ in range(3):
            sa.request(lambda: repro.array(ha) + 1.0).result()
        sb.request(lambda: repro.array(hb) * 2.0).result()
        assert sa.stats.n_requests == 3 and sa.stats.latency.count == 3
        assert sb.stats.n_requests == 1 and sb.stats.latency.count == 1
        assert sa.stats.n_failed == 0 and sb.stats.n_failed == 0
        # each tenant's WaitStats folded only its own drained cones
        assert sa.stats.n_flushes == 3
        assert sb.stats.n_flushes == 1
        assert sa.stats.wait.n_compute_ops > sb.stats.wait.n_compute_ops
        stats = srv.stats()
        assert list(stats) == ["a", "b"]
        rendered = srv.format_stats()
        assert "latency:" in rendered and "a" in rendered


def test_concurrent_tenants_bit_identical_under_threads():
    results = {}
    with Server(nprocs=4, block_size=16, latency=1e-3,
                max_inflight=8, max_queue=64) as srv:
        def client(name, seed):
            rng = np.random.default_rng(seed)
            h = rng.standard_normal((32, 32))
            sess = srv.session(name)

            def fn():
                a = repro.array(h)
                return np.roll(a, 1, axis=1) * 3.0 - a

            got = [sess.request(fn).result() for _ in range(3)]
            exp = np.roll(h, 1, axis=1) * 3.0 - h
            results[name] = all(np.array_equal(g, exp) for g in got)

        threads = [
            threading.Thread(target=client, args=(f"c{i}", i))
            for i in range(6)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert all(results.values()), results
        assert srv.admission.peak_inflight >= 2  # cones actually overlapped


# ---------------------------------------------------------------------------
# work stealing
# ---------------------------------------------------------------------------


def test_steal_rebalances_single_owner_skew():
    """Many independent single-block chains all owned by worker 0 land
    in its queue while it is provably busy with another cone's slow op:
    idle workers must steal from that queue (the latency-aware threshold
    permits it — plenty of queued work), and results stay exact.

    The busy op is essential for determinism: when the owner is parked,
    it races the thieves for its own freshly-pushed batch and usually
    wins (a whole-queue pop is one lock acquisition), so steals would be
    a coin flip.  Pinning the owner inside a long payload leaves the
    skewed queue exposed for the full sleep."""
    from repro.core.ufunc import UFunc

    slow = UFunc(
        name="slow_for_steal_test",
        fn=lambda x: (time.sleep(0.25), x + 1.0)[1],
        nin=1,
    )
    with repro.runtime(nprocs=4, block_size=8, flush="async") as rt:
        busy = repro.ones((8,))  # single-block: owned by worker 0
        rt.record_map(slow, (busy._base, busy._view),
                      [(busy._base, busy._view)])
        t_busy = rt.flush(wait=False, targets=[busy])
        # worker 0 is now inside the 250 ms payload; every chain below is
        # also owned by worker 0, so this flush piles 96 ready fills onto
        # its queue and wakes the (empty-queue) thieves
        arrs = [repro.ones((8,)) for _ in range(96)]
        for _ in range(4):
            for a in arrs:
                a += 1.0
        t_chains = rt.flush(wait=False, targets=list(arrs))
        t_chains.wait()
        t_busy.wait()
        st = rt.stats()
        assert st.n_stolen > 0, (
            "no ops were stolen from the overloaded owner's queue"
        )
        np.testing.assert_array_equal(np.asarray(busy), np.full((8,), 2.0))
        for a in arrs:
            np.testing.assert_array_equal(np.asarray(a), np.full((8,), 5.0))


def test_steal_disabled_is_bit_identical_and_never_steals():
    def run(steal):
        with repro.runtime(nprocs=4, block_size=8, flush="async",
                           steal=steal) as rt:
            arrs = [repro.ones((8,)) + float(i) for i in range(64)]
            rt.flush()
            st = rt.stats()
            return [np.asarray(a).copy() for a in arrs], st

    with_steal, st_on = run(True)
    without, st_off = run(False)
    assert st_off.n_stolen == 0 and st_off.n_steals == 0
    for x, y in zip(with_steal, without):
        np.testing.assert_array_equal(x, y)


def test_steal_preserves_comm_first_stencil_results():
    """A comm-heavy stencil under steal=True vs steal=False: stolen
    batches are re-sorted comm-first, and any interleaving of
    simultaneously-ready ops is bit-identical by the cone invariant."""
    host = np.arange(4096.0).reshape(64, 64)

    def run(steal):
        with repro.runtime(nprocs=4, block_size=16, flush="async",
                           steal=steal, steal_threshold=2):
            a = repro.array(host)
            b = (np.roll(a, 1, axis=0) + np.roll(a, -1, axis=0)) * 0.5
            c = (np.roll(b, 1, axis=1) + np.roll(b, -1, axis=1)) * 0.5
            return np.asarray(c).copy()

    np.testing.assert_array_equal(run(True), run(False))


def test_policy_steal_knobs_validated():
    from repro.api.config import ExecutionPolicy

    with pytest.raises(ValueError, match="steal_threshold"):
        ExecutionPolicy(steal_threshold=1)
    with pytest.raises(ValueError, match="steal_latency"):
        ExecutionPolicy(steal_latency=-1.0)
    p = ExecutionPolicy(steal=False, steal_threshold=8, steal_latency=1e-3)
    assert not p.steal and p.steal_threshold == 8


# ---------------------------------------------------------------------------
# concurrent cone drains at the engine level (serve's substrate)
# ---------------------------------------------------------------------------


def test_disjoint_cones_drain_concurrently():
    with repro.runtime(nprocs=2, block_size=8, flush="async",
                       latency=20e-3) as rt:
        a = repro.ones((16,)) + 1.0
        b = repro.ones((16,)) + 2.0
        ta = rt.flush(wait=False, targets=[a])
        tb = rt.flush(wait=False, targets=[b])
        # both slow drains in flight at once: disjoint cones NOT joined
        assert rt._exec_executor_obj.n_active_drains == 2
        ta.wait()
        tb.wait()
        np.testing.assert_array_equal(np.asarray(a), np.full((16,), 2.0))
        np.testing.assert_array_equal(np.asarray(b), np.full((16,), 3.0))


def test_conflicting_cone_joins_inflight_writer():
    with repro.runtime(nprocs=2, block_size=8, flush="async",
                       latency=10e-3) as rt:
        a = repro.ones((16,)) + 1.0
        t1 = rt.flush(wait=False, targets=[a])
        a += 5.0  # second cone writes the same base: conflicts with t1
        t2 = rt.flush(wait=False, targets=[a])
        assert t1.done()  # the conflicting flush joined it first
        t2.wait()
        np.testing.assert_array_equal(np.asarray(a), np.full((16,), 7.0))


# ---------------------------------------------------------------------------
# planning off the record lock: lock-hold accounting, plan-shape cache,
# cross-tenant cone batching
# ---------------------------------------------------------------------------


def test_request_lock_hold_histogram_populated():
    """The record lock is held only for recording + cone extraction; the
    server measures each hold and the histogram must fill up."""
    with Server(nprocs=2, block_size=8) as srv:
        sess = srv.session("t")
        h = np.arange(16.0)
        for _ in range(4):
            sess.request(lambda: repro.array(h) * 2.0).result()
        assert srv.lock_hold.count == 4
        assert srv.lock_hold.max < 10.0  # sane seconds, not garbage
        assert srv.lock_hold.quantile(0.5) > 0.0


def test_server_repeated_shape_hits_plan_cache():
    with Server(nprocs=2, block_size=8, plan_cache=True) as srv:
        sess = srv.session("t")
        h = np.arange(32.0)

        def fn():
            a = repro.array(h)
            return np.roll(a, 1, axis=0) + a * 2.0

        exp = np.roll(h, 1, axis=0) + h * 2.0
        for _ in range(5):
            np.testing.assert_array_equal(sess.request(fn).result(), exp)
        cache = srv.runtime._plan_cache
        assert cache is not None
        assert cache.hits >= 3  # identical shape after warmup
        assert cache.misses >= 1


def test_server_batch_cones_end_to_end_correct():
    results = {}
    with Server(nprocs=4, block_size=16, latency=1e-3,
                batch_cones=True, max_inflight=8, max_queue=64) as srv:
        def client(name, seed):
            rng = np.random.default_rng(seed)
            h = rng.standard_normal((32, 32))
            sess = srv.session(name)

            def fn():
                a = repro.array(h)
                return np.roll(a, 1, axis=1) * 3.0 - a

            got = [sess.request(fn).result() for _ in range(4)]
            exp = np.roll(h, 1, axis=1) * 3.0 - h
            results[name] = all(np.array_equal(g, exp) for g in got)

        threads = [
            threading.Thread(target=client, args=(f"c{i}", i))
            for i in range(6)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert all(results.values()), results
        batcher = srv.runtime._batcher
        assert batcher is not None
        assert batcher.n_batches >= 1


def test_submit_failure_fails_ticket_and_releases_admission():
    """A cone that fails verification *after* the record lock is
    released must still fail the request future and hand the admission
    slot back."""
    with Server(nprocs=2, block_size=8, max_inflight=1,
                verify="full") as srv:
        sess = srv.session("t")
        h = np.arange(16.0)
        got = sess.request(lambda: repro.array(h) + 1.0).result()
        np.testing.assert_array_equal(got, h + 1.0)
        assert srv.admission.inflight == 0


def test_engine_ticket_wait_before_bind_blocks_then_resolves():
    """A ticket returned while its cone is still being planned parks
    wait() until the executor future is bound, then yields stats."""
    with repro.runtime(nprocs=2, block_size=8, flush="async",
                       latency=5e-3) as rt:
        a = repro.ones((16,)) * 2.0
        t = rt.flush(wait=False, targets=[a])
        res = t.wait()
        assert t.done()
        assert res is not None
        np.testing.assert_array_equal(np.asarray(a), np.full((16,), 2.0))
