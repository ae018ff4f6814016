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
    is_fill_record,
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


# the creation routines that call ``fill_base``, each with the value it
# fills with
_FILLS = {
    "empty": (lambda shape: repro.empty(shape, dtype=np.float32), 0.0),
    "zeros": (lambda shape: repro.zeros(shape, dtype=np.float32), 0.0),
    "full": (lambda shape: repro.full(shape, 2.5, dtype=np.float32), 2.5),
}


def _blocks(rt, arr):
    return {k: b for k, b in rt.storage.items() if k[0] == arr._base.id}


@pytest.mark.parametrize("make", list(_FILLS))
def test_fills_hold_no_host_block_and_are_made_on_first_touch(make):
    """On the device path a filled array is fill records, no writable
    host block; the payloads of a drain make on the device exactly the
    blocks they touch, and no fill writes host bytes."""
    create, value = _FILLS[make]
    with _jax_runtime() as rt:
        t = create((64, 64))  # 16 blocks of 16x16
        assert not any(isinstance(b, np.ndarray) and b.flags.writeable
                       for b in rt.storage.values())
        assert all(is_fill_record(b) for b in _blocks(rt, t).values())
        top = t[:32] * 2.0  # reads t's 8 upper blocks, writes 8 blocks
        got = np.asarray(top)
        stats = rt.backend_stats()
        on_device = [k for k, b in rt.storage.items()
                     if not isinstance(b, np.ndarray)]
        lazy = [k for k, b in _blocks(rt, t).items()
                if isinstance(b, np.ndarray) and is_fill_record(b)]
    np.testing.assert_array_equal(got, np.full((32, 64), 2 * value))
    assert stats["n_fill_device"] == len(on_device) == 16
    assert len(lazy) == 8  # the lower half, which no payload touched
    assert stats["fill_host_bytes"] == 0 and stats["h2d_bytes"] == 0


def test_an_untouched_fill_reads_as_its_value_and_stays_lazy():
    """``np.asarray`` of a filled array no payload touched reads the
    value from the fill record: no readback, no host copy stored."""
    with _jax_runtime() as rt:
        t = repro.full((48, 40), 3.0)
        with trace() as tr:
            got = np.asarray(t)
        spans = tr.span_totals()
        blocks = _blocks(rt, t)
        stats = rt.backend_stats()
    np.testing.assert_array_equal(got, np.full((48, 40), 3.0))
    assert got.dtype == np.float64
    assert blocks and all(is_fill_record(b) for b in blocks.values())
    assert "exec.readback" not in spans
    assert stats.get("d2h_bytes", 0) == 0
    assert stats.get("fill_host_bytes", 0) == 0


def test_a_float64_fill_keeps_its_declared_dtype_without_x64():
    """The planner reads float64 for a float64 fill before its first
    touch (the record's dtype) and after it (the store's ``declared``),
    though the device block is float32."""
    assert not jax.config.jax_enable_x64
    with _jax_runtime() as rt:
        t = repro.zeros((32, 32))
        key = (t._base.id, (0, 0))
        before = block_dtype(rt.storage, key)
        t += 1.0
        got = np.asarray(t)
        after = block_dtype(rt.storage, key)
        dev = rt.storage[key].dtype
    assert before == after == np.float64 and dev == np.float32
    np.testing.assert_array_equal(got, np.ones((32, 32)))


@pytest.mark.parametrize("reduced", [False, True], ids=["map", "map_reduce"])
def test_a_host_fallback_reads_a_fill_record(reduced):
    """A ufunc with no ``jnp`` form over a filled array no payload
    touched runs on NumPy over the fill records, downloads nothing, and
    gives NumPy's answer."""
    want_block = np.exp(np.full((32, 32), 0.5, dtype=np.float32))
    want = want_block.sum() if reduced else want_block
    with _jax_runtime(nprocs=2) as rt:
        np.asarray(repro.array(np.ones((4, 4), np.float32)) + 1.0)
        rt._exec_backend_obj._impls.pop("exp")
        a = repro.full((32, 32), 0.5, dtype=np.float32)
        before = rt.backend_stats()
        got = float(np.sum(np.exp(a))) if reduced else np.asarray(np.exp(a))
        after = rt.backend_stats()
    np.testing.assert_allclose(got, want, rtol=1e-6)
    assert after["n_host_untranslated"] - before["n_host_untranslated"] >= 4
    # the result's read at gather alone
    assert after["d2h_bytes"] - before["d2h_bytes"] == np.asarray(want).nbytes
    assert after["fill_host_bytes"] == 0


def test_disjoint_writers_of_one_fill_record_give_the_numpy_grid():
    """16 workers write the rows of one block that is still a fill
    record: they race to make it on the device, and every row lands as
    the NumPy backend writes it."""
    n = 64
    src = np.arange(n * n, dtype=np.float32).reshape(n, n)
    grids = {}
    prev = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        for backend in ("numpy", "jax"):
            with repro.runtime(
                    RuntimeConfig(nprocs=16, block_size=n),
                    ExecutionPolicy(flush="async", backend=backend, passes=(),
                                    steal_threshold=2, steal_latency=0.0)):
                x = repro.array(src)
                out = repro.full((n, n), -1.0, dtype=np.float32)
                for i in range(0, n, 2):
                    out[i, :] = x[i, :] * 2.0
                grids[backend] = np.asarray(out)
    finally:
        sys.setswitchinterval(prev)
    want = src * 2.0
    want[1::2] = -1.0
    np.testing.assert_array_equal(grids["numpy"], want)
    np.testing.assert_array_equal(grids["jax"], grids["numpy"])


@pytest.mark.parametrize("flush,backend", [
    ("async", "numpy"), ("async", "auto"), ("sim", "numpy"), ("sim", "jax"),
])
def test_host_paths_keep_writable_filled_blocks(flush, backend):
    """Where payloads write host blocks in place (the NumPy and auto
    backends, the simulated flush) ``fill_base`` writes them eagerly and
    counts their bytes."""
    with repro.runtime(RuntimeConfig(nprocs=4, block_size=16),
                       ExecutionPolicy(flush=flush, backend=backend)) as rt:
        t = repro.zeros((32, 32))
        blocks = _blocks(rt, t)
        assert len(blocks) == 4
        for b in blocks.values():
            assert b.flags.writeable and not is_fill_record(b)
            np.testing.assert_array_equal(b, np.zeros((16, 16)))
        assert rt.fill_host_bytes == 32 * 32 * 8
        t += 1.0
        got = np.asarray(t)
    np.testing.assert_array_equal(got, np.ones((32, 32)))
