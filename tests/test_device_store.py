"""Device-resident blocks under ``backend="jax"``: blocks move to the
device on first touch and stay there, every payload kind runs on device
arrays, writes land through one locked update, and bytes cross to the
host only at ``gather`` or where a payload has no device form."""
import sys

import jax
import numpy as np
import pytest

import repro
from benchmarks.paper_apps import APPS, run_app
from repro.api import ExecutionPolicy, RuntimeConfig
from repro.core.blocks import Fragment
from repro.core.engine import (
    BlockStore,
    FillPayload,
    MapPayload,
    TransferPayload,
    block_dtype,
)
from repro.core.ufunc import get_ufunc
from repro.exec import JaxBackend
from repro.obs import trace

from tests.test_exec import SMALL, SMALL_BLOCKS

# the matmuls of these apps sum in XLA's order, not BLAS's
_MATMUL_ORDER = {"knn", "jacobi"}


def _jax_runtime(nprocs=4, block_size=16, fusion=False, **policy):
    return repro.runtime(
        RuntimeConfig(nprocs=nprocs, block_size=block_size, fusion=fusion),
        ExecutionPolicy(flush="async", backend="jax", **policy),
    )


@pytest.fixture
def x64():
    prev = jax.config.jax_enable_x64
    jax.config.update("jax_enable_x64", True)
    try:
        yield
    finally:
        jax.config.update("jax_enable_x64", prev)


@pytest.mark.parametrize("app", list(APPS))
def test_paper_apps_under_x64_equal_numpy(app, x64):
    """In float64 the device store computes what the NumPy backend
    computes, bit for bit, but for the order a matmul sums in."""
    kw = dict(mode="latency_hiding", nprocs=4, block_size=SMALL_BLOCKS[app],
              flush_backend="async", **SMALL[app])
    _, ref = run_app(app, exec_backend="numpy", **kw)
    _, got = run_app(app, exec_backend="jax", **kw)
    assert got.dtype == ref.dtype == np.float64
    if app in _MATMUL_ORDER:
        np.testing.assert_allclose(got, ref, rtol=1e-12)
    else:
        assert np.array_equal(got, ref, equal_nan=True)


def test_fused_stencil_under_x64_equals_numpy(x64):
    host = np.random.default_rng(5).random((34, 34))
    results = []
    for backend in ("numpy", "jax"):
        with repro.runtime(RuntimeConfig(nprocs=4, block_size=8, fusion=True),
                           ExecutionPolicy(flush="async", backend=backend)):
            grid = repro.array(host)
            c, n, s = grid[1:-1, 1:-1], grid[:-2, 1:-1], grid[2:, 1:-1]
            w, e = grid[1:-1, :-2], grid[1:-1, 2:]
            for _ in range(3):
                work = 0.2 * ((((c + n) + s) + w) + e)
                delta = float(np.sum(np.abs(work - c)))
                c[:] = work
            results.append((np.asarray(grid), delta))
    (ref, dref), (got, dgot) = results
    assert np.array_equal(got, ref) and dgot == dref


def test_a_drain_over_device_blocks_moves_no_bytes_but_the_read():
    """Blocks cross once, at first touch; after that a drain uploads
    nothing, and ``gather`` reads back each block it touches once."""
    x = np.arange(64 * 64, dtype=np.float32).reshape(64, 64)
    with _jax_runtime() as rt:
        a, b = repro.array(x), repro.array(x + 1.0)
        np.asarray(a + b)  # both move to the device
        first = rt.backend_stats()
        c = a * b + a
        with trace() as tr:
            got = np.asarray(c)
        after = rt.backend_stats()
        spans = tr.span_totals()
    np.testing.assert_array_equal(got, x * (x + 1.0) + x)
    assert first["h2d_bytes"] == 2 * x.nbytes and first["n_staged"] > 0
    assert after["h2d_bytes"] == first["h2d_bytes"]
    assert after["n_staged"] == first["n_staged"]
    assert after["d2h_bytes"] - first["d2h_bytes"] == x.nbytes
    assert spans["exec.readback"][0] == 1  # one read, at the gather
    assert "exec.host" not in spans


def test_filled_blocks_are_made_on_the_device():
    """A ``zeros`` temporary never crosses: its blocks are created on the
    device, and the planner still reads the dtype the program declared."""
    with _jax_runtime() as rt:
        t = repro.zeros((32, 32))  # float64, float32 on the device
        t += 1.0
        got = np.asarray(t)
        stats = rt.backend_stats()
        key = (t._base.id, (0, 0))
        assert rt.storage[key].dtype == np.float32
        assert block_dtype(rt.storage, key) == np.float64
    np.testing.assert_array_equal(got, np.ones((32, 32)))
    assert stats["h2d_bytes"] == 0 and stats["n_staged"] == 0


def _backend():
    storage, scratch = BlockStore(), {}
    storage[(1, (0,))] = np.arange(8, dtype=np.float32)
    return JaxBackend(storage, scratch), storage, scratch


class _Op:
    def __init__(self, payload):
        self.payload = payload


def test_a_transfer_snapshots_its_source():
    """A transfer's scratch keeps the values the block had when it was
    sent, whatever is written to the block after: the payload that reads
    the scratch, run after that write, sees the old values."""
    backend, storage, _ = _backend()
    storage[(2, (0,))] = np.zeros(4, dtype=np.float32)
    frag = Fragment(block=(0,), local=((2, 6, 1),), owner=0)
    whole = Fragment(block=(0,), local=((0, 4, 1),), owner=0)
    backend.transfer(TransferPayload(("b", 1, frag), dst_scratch=7))
    backend.execute(_Op(FillPayload(out_base=1, out_frag=frag, value=-1.0)))
    backend.execute(_Op(MapPayload(get_ufunc("identity"), 2, whole,
                                   (("s", 7),), np.dtype(np.float32))))
    np.testing.assert_array_equal(np.asarray(storage[(1, (0,))]),
                                  [0, 1, -1, -1, -1, -1, 6, 7])
    np.testing.assert_array_equal(np.asarray(storage[(2, (0,))]),
                                  [2, 3, 4, 5])
    assert backend.stats()["n_transfer"] == 1


def test_disjoint_writers_of_one_block_lose_no_write():
    """Rows of one block written by many workers at once (all owned by
    one rank, so the others steal them): every row lands."""
    n = 64
    src = np.arange(n * n, dtype=np.float32).reshape(n, n)
    prev = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        stolen = 0
        for _ in range(3):
            with _jax_runtime(nprocs=8, block_size=n, passes=(),
                              steal_threshold=2, steal_latency=0.0) as rt:
                x = repro.array(src)
                out = repro.zeros((n, n), dtype=np.float32)
                np.asarray(out + x)  # both blocks on the device
                for i in range(n):
                    out[i, :] = x[i, :] * 2.0
                got = np.asarray(out)
                stolen += rt.stats().n_stolen
            np.testing.assert_array_equal(got, src * 2.0)
    finally:
        sys.setswitchinterval(prev)
    assert stolen > 0


@pytest.mark.parametrize("reduced", [False, True], ids=["map", "map_reduce"])
def test_untranslatable_ufunc_falls_back_and_is_counted(reduced):
    """A ufunc with no ``jnp`` form runs on NumPy over downloaded inputs
    (a map, or a map fused with its reduction), is counted as staged,
    and its result is right."""
    host = np.linspace(0.0, 1.0, 32 * 32, dtype=np.float32).reshape(32, 32)
    want = np.exp(host).sum() if reduced else np.exp(host)
    with _jax_runtime(nprocs=2) as rt:
        a = repro.array(host)
        np.asarray(a + 1.0)  # builds the backend; moves a
        rt._exec_backend_obj._impls.pop("exp")
        before = rt.backend_stats()
        with trace() as tr:
            # a dead temporary's map fuses with its reduction
            got = float(np.sum(np.exp(a))) if reduced else np.asarray(np.exp(a))
        after = rt.backend_stats()
        spans = tr.span_totals()
    np.testing.assert_allclose(got, want, rtol=1e-5)
    fallbacks = after["n_host_untranslated"] - before["n_host_untranslated"]
    assert fallbacks >= 4  # one per 16x16 block
    assert after["n_staged"] - before["n_staged"] == fallbacks
    assert after["d2h_bytes"] > before["d2h_bytes"]
    assert spans["exec.host"][0] == fallbacks


def test_dead_temporaries_are_recycled_between_barriers():
    """A demand-driven loop that never reaches a barrier keeps its block
    storage and scratch bounded: what no pending or in-flight operation
    touches is recycled after each read."""
    host = np.random.default_rng(1).random((34, 34)).astype(np.float32)
    sizes = []
    with _jax_runtime(fusion=True, block_size=8) as rt:
        grid = repro.array(host)
        c, n, s = grid[1:-1, 1:-1], grid[:-2, 1:-1], grid[2:, 1:-1]
        w, e = grid[1:-1, :-2], grid[1:-1, 2:]
        for _ in range(4):
            work = 0.2 * ((((c + n) + s) + w) + e)
            float(np.sum(np.abs(work - c)))
            c[:] = work  # still pending at the next read
            sizes.append((len(rt.storage), len(rt.scratch)))
    assert sizes[1] == sizes[2] == sizes[3]
