"""Asynchronous flush executor and pluggable compute backends.

:class:`AsyncExecutor` drains a recorded
:class:`~repro.core.graph.DependencySystem` with genuine concurrency —
the wall-clock counterpart of ``repro.core.scheduler.run_schedule``:

* one :class:`~repro.exec.workers.Worker` thread per simulated process,
  each with a private comm-first ready queue;
* transfers go through a :mod:`~repro.exec.channels` discipline — the
  non-blocking :class:`AsyncChannel` progress engine delivers scratch
  buffers while compute runs, the :class:`BlockingChannel` reproduces the
  synchronous baseline on the worker's own clock;
* completion is sweep-based: a finished worker batch (or a channel
  future's done-callback) performs the refcount decrements
  (``deps.complete``) and dispatches newly-ready operations — the
  graph's ``on_ready`` hook delivers them straight to worker queues,
  no central scheduler loop.  Under the ``"batch"`` plan pass the
  sweep moves per-worker *lists* per lock round trip
  (``batch_dispatch=True``), amortizing the Python handoff overhead;
* the numerical result is bit-identical to the simulated executor's: the
  dependency system totally orders every pair of conflicting accesses, so
  any schedule that respects it interprets the payloads (shared
  ``repro.core.engine.execute_payload``) into the same block contents.

Deadlock is detected structurally, not by timeout: when nothing is in
flight and the dependency system still has pending operations, no future
can ever resolve — the executor raises
:class:`~repro.core.scheduler.DeadlockError` listing the stuck
operation-nodes.  :func:`run_rendezvous_bsp_async` applies the same
treatment to the paper's fig. 6 schedule executed with real threads and
two-sided rendezvous messaging.
"""
from __future__ import annotations

import itertools
import re
import threading
import time
from typing import NamedTuple, Optional

import numpy as np

from repro.api.registry import get_backend, register_backend
from repro.core.engine import (
    CoalescedTransferPayload,
    CombinePayload,
    FillPayload,
    FusedMapReducePayload,
    MapPayload,
    MatmulPayload,
    ReducePartialPayload,
    execute_payload,
    is_fill_record,
    resolve_ref,
)
from repro.core.graph import COMM, DependencySystem, OperationNode
from repro.core.scheduler import DeadlockError, format_stuck_ops
from repro.obs import collector as _obs

from .channels import RendezvousDeadlock, RendezvousMailbox, make_channel
from .futures import Future
from .stats import WaitStats, WorkerStats
from .workers import Worker

__all__ = [
    "ComputeBackend",
    "NumpyBackend",
    "JaxBackend",
    "AutoBackend",
    "make_backend",
    "AsyncExecutor",
    "run_rendezvous_bsp_async",
]


# ---------------------------------------------------------------------------
# Compute backends
# ---------------------------------------------------------------------------


class ComputeBackend:
    """Executes operation payloads against the runtime's block storage."""

    name = "abstract"
    # whether the backend makes a filled block from the runtime's fill
    # record (``repro.core.engine.fill_record``) instead of writing into
    # a filled host block
    lazy_fills = False

    def __init__(self, storage: dict, scratch: dict):
        self.storage = storage
        self.scratch = scratch

    def execute(self, op: OperationNode) -> None:
        raise NotImplementedError

    def transfer(self, p) -> None:
        """Run a transfer payload (on a channel's thread): the NumPy
        interpreter's copy into scratch, unless a backend keeps its
        blocks elsewhere."""
        execute_payload(p, self.storage, self.scratch)

    def stats(self) -> dict:
        """Per-path payload counters (empty for a backend without any)."""
        return {}


class NumpyBackend(ComputeBackend):
    """Eager NumPy interpretation — the reference backend (bit-identical
    to the simulated executor by construction)."""

    name = "numpy"

    def execute(self, op: OperationNode) -> None:
        execute_payload(op.payload, self.storage, self.scratch)


# a lock per block, striped over this many locks: a block's lock guards
# its read-update-replace
_LOCK_STRIPES = 256
# the narrowest fragment the Pallas stencil kernel takes (one sublane
# tile); narrower halo slivers take the jitted jnp path
_STENCIL5_MIN_DIM = 8


def _slices(local) -> tuple:
    return tuple(slice(s, e, st) for s, e, st in local)


def _cut(x, local):
    """``x`` cut to a fragment's per-dim ``(start, stop, step)``; the
    whole of ``x`` for None.  Runs while a program traces."""
    return x if local is None else x[_slices(local)]


def _cut_shape(shape, local):
    if local is None:
        return shape
    return tuple(-(-(e - s) // st) for s, e, st in local)


class _Sent(NamedTuple):
    """A transfer's scratch under :class:`JaxBackend`: the source block
    as it was sent (immutable) and the fragment's per-dim
    ``(start, stop, step)``."""

    block: object
    local: tuple


class _HostCopies(dict):
    """Host copies of the blocks (or scratch buffers) of ``src`` that a
    host fallback reads, each downloaded on its first access; the bytes
    read from the device go to ``moved``.  A host block, a fill record
    among them, is read where it is."""

    def __init__(self, src: dict, moved: list):
        super().__init__()
        self._src = src
        self._moved = moved

    def __missing__(self, key):
        x = self._src[key]
        if not isinstance(x, np.ndarray):
            host = np.asarray(x.block if isinstance(x, _Sent) else x)
            self._moved.append(host.nbytes)
            x = host[_slices(x.local)] if isinstance(x, _Sent) else host
        self[key] = x
        return x


class JaxBackend(ComputeBackend):
    """Runs every payload with XLA on device-resident blocks.

    * **Blocks live on the device.**  The first payload that touches a
      block holding a host ``np.ndarray`` uploads it once, in the dtype
      the device computes in, and replaces the storage entry with the
      device array.  ``fill_base`` writes no host block for this
      backend (:attr:`lazy_fills`): it stores a fill record, and the
      first payload that touches the block makes it on the device from
      the record (``n_fill_device``).  Bytes cross to the host only
      where the program reads values (:meth:`readback`, called by
      ``Runtime.gather``) and where a payload has no device form.
    * **One program per payload shape.**  Each payload is a jitted
      program over whole blocks, cut to its fragments inside the
      program.  Programs are cached by the payload's kind and ufunc and
      each operand's shape, dtype and block-local slice — never by block
      coordinate — so every block of one cut pattern shares a program.
      Fused 5-point stencils of 32-bit (or narrower) dtype over
      fragments of at least 8 rows and columns run the Pallas
      ``stencil5_block`` kernel (interpreted on the CPU platform); halo
      slivers, and float64 under ``jax_enable_x64`` (Mosaic has no
      64-bit types), take the jitted ``jnp`` path.
    * **One write point.**  A write is the functional update
      ``storage[key] = blk.at[slices].set(value)``, a jitted program run
      under the block's lock: two workers may write disjoint fragments
      of one block at once.  :meth:`_store` is where a map payload's
      result lands in its block.
    * **No blocking, no copies in transit.**  Workers dispatch and
      return; nothing waits on a result between a drain's payloads.  A
      transfer (:meth:`transfer`, on the channel's threads) puts the
      source block as it is at send time, with the fragment's slice,
      into scratch: a ``jax.Array`` is immutable, so that is the
      send-time snapshot, and the program that reads the scratch cuts
      it.
    * **Host fallback.**  A map with a ufunc that has no ``jnp`` form
      downloads its inputs, runs NumPy, and uploads its result through
      :meth:`_store`.

    * **Matmuls at their dtype's precision.**  A float32 product runs
      at ``Precision.HIGHEST``; other dtypes keep XLA's default (on a
      TPU, one bfloat16 pass).

    :meth:`stats` counts payloads per path, matmul and combine payloads
    among them (``n_matmul``, ``n_combine``), transfers, blocks made on
    the device from a fill record (``n_fill_device``), the payloads
    that moved bytes between host and device (``n_staged``: fallbacks
    and first-touch uploads) and those bytes, readbacks included.
    Programs are named after their kind (``repro_map_<ufunc>``,
    ``repro_stencil5``, ``repro_update``, ``repro_matmul``, ...), so a
    device trace names them.

    With a trace collector active or a JAX profiler recording, each
    payload runs under stage spans: ``exec.stage`` (resolving refs into
    the program's arguments, first-touch uploads included),
    ``exec.launch`` (the program's dispatch and the block update), and
    ``exec.host`` for a payload that runs on NumPy.  A combine's
    staging and its launch each run under an ``exec.combine`` span too,
    nested in its ``exec.stage`` and ``exec.launch``: two a combine.

    Note: without ``jax_enable_x64`` the payloads compute in float32, so
    results are *numerically close*, not bit-identical, to the NumPy
    backend on float64 programs.
    """

    name = "jax"
    lazy_fills = True

    def __init__(self, storage: dict, scratch: dict):
        super().__init__(storage, scratch)
        import jax
        import jax.numpy as jnp

        from repro.compile_cache import enable_compile_cache
        from repro.kernels import resolve_interpret
        from repro.kernels.stencil import stencil5_block

        enable_compile_cache()
        self._jax = jax
        self._jnp = jnp
        self._impls = {
            "identity": lambda x: x,
            "add": jnp.add,
            "subtract": jnp.subtract,
            "multiply": jnp.multiply,
            "divide": jnp.divide,
            "power": jnp.power,
            "negative": jnp.negative,
            "absolute": jnp.abs,
            "exp": jnp.exp,
            "log": jnp.log,
            "sqrt": jnp.sqrt,
            "square": jnp.square,
            "maximum": jnp.maximum,
            "minimum": jnp.minimum,
            # comparisons carry a real bool result dtype (UFunc.out_dtype),
            # matching NumPy — no float cast
            "greater": jnp.greater,
            "less": jnp.less,
            "where": jnp.where,
        }
        self._reductions = {
            "add": jnp.sum,
            "multiply": jnp.prod,
            "maximum": jnp.max,
            "minimum": jnp.min,
        }
        self._programs: dict = {}
        self._stencil5 = stencil5_block
        self.interpret = resolve_interpret(None)
        self._locks = [threading.Lock() for _ in range(_LOCK_STRIPES)]
        self._stages = {
            MapPayload: self._stage_map,
            FusedMapReducePayload: self._stage_fused,
            ReducePartialPayload: self._stage_reduce,
            CombinePayload: self._stage_combine,
            FillPayload: self._stage_fill,
            MatmulPayload: self._stage_matmul,
        }
        # payload counters, bumped from every worker thread
        self._count_lock = threading.Lock()
        self._counts = dict(
            n_jit=0,  # payloads run as jitted XLA programs
            n_pallas=0,  # fused stencils run through the Pallas kernel
            n_host_untranslated=0,  # maps with no jnp form, run by NumPy
            n_transfer=0,  # transfers, run on the channel's threads
            n_matmul=0,  # matmul payloads (also in n_jit)
            n_combine=0,  # combines of partial reductions (also in n_jit)
            n_fill_device=0,  # blocks made on the device from a fill record
            n_staged=0,  # payloads that moved bytes host<->device
            h2d_bytes=0,  # first-touch uploads and host results
            d2h_bytes=0,  # readbacks at gather and fallback inputs
        )

    # -- helpers ---------------------------------------------------------
    def _count(self, key: Optional[str] = None, h2d: int = 0, d2h: int = 0,
               staged: bool = False, kind: Optional[str] = None) -> None:
        """Bump the path counter ``key``, the counter ``kind`` that a
        profiler session sums too (``n_matmul``, ``n_combine``,
        ``n_fill_device``) and the byte counters."""
        with self._count_lock:
            c = self._counts
            if key is not None:
                c[key] += 1
            if kind is not None:
                c[kind] += 1
            c["n_staged"] += staged
            c["h2d_bytes"] += h2d
            c["d2h_bytes"] += d2h
        _obs.count("h2d_bytes", h2d)  # a profiler session's share
        _obs.count("d2h_bytes", d2h)
        if kind is not None:
            _obs.count(kind, 1)

    def stats(self) -> dict:
        """Payload counts per execution path, transfers, staged payloads,
        host↔device bytes, and whether the Pallas kernel runs
        interpreted (only on the CPU platform)."""
        with self._count_lock:
            return dict(self._counts, interpret=self.interpret)

    def _program(self, key, build):
        """The program cached under ``key``, built on a miss (workers
        that race to build one all take the first stored)."""
        try:
            return self._programs[key]
        except KeyError:
            return self._programs.setdefault(key, build())

    def _upload(self, x: np.ndarray):
        # without x64, device_put narrows float64/int64 to 32 bits on the
        # host as it uploads: no convert program runs on the device
        self._count(h2d=x.nbytes)
        return self._jax.device_put(x)

    def _device_block(self, key, moved: list):
        """Block ``key`` as a device array: a host block moves to the
        device on first touch (the bytes it uploads go to ``moved``)."""
        blk = self.storage[key]
        if isinstance(blk, np.ndarray):
            with self._locks[hash(key) % _LOCK_STRIPES]:
                blk = self.storage[key]
                if isinstance(blk, np.ndarray):
                    blk = self._migrate(key, blk, moved)
        return blk

    def _migrate(self, key, host: np.ndarray, moved: list):
        if is_fill_record(host):
            dtype = self._jax.dtypes.canonicalize_dtype(host.dtype)
            full = self._program(
                ("full", host.shape, dtype),
                lambda: self._jax.jit(_repro_fill(self._jnp, host.shape, dtype)),
            )
            dev = full(host[(0,) * host.ndim])
            self._count(kind="n_fill_device")
        else:
            dev = self._jax.device_put(host)
            moved.append(host.nbytes)
        declared = getattr(self.storage, "declared", None)
        if declared is not None and dev.dtype != host.dtype:
            declared[key] = host.dtype
        self.storage[key] = dev
        return dev

    def _operand(self, ref, moved: list):
        """A payload input as a program takes it, ``(value, signature)``:
        a whole device block (cut inside the program), a scratch buffer,
        or a constant (signature None)."""
        kind = ref[0]
        if kind == "b":
            _, bid, frag = ref
            blk = self._device_block((bid, frag.block), moved)
            return blk, (blk.shape, blk.dtype, frag.local)
        if kind == "s":
            x = self.scratch[ref[1]]
            if isinstance(x, _Sent):
                return x.block, (x.block.shape, x.block.dtype, x.local)
            return x, (x.shape, x.dtype, None)
        return ref[1], None

    def _write(self, key, local, value, combine: Optional[str] = None) -> None:
        """Update a device block's fragment: ``blk.at[local].set(value)``,
        or with ``combine(blk[local], value)``, under the block's lock."""
        update = self._program(
            ("update", local, combine),
            lambda: self._jax.jit(_repro_update(
                self._jnp, local,
                None if combine is None else self._impls[combine])),
        )
        with self._locks[hash(key) % _LOCK_STRIPES]:
            self.storage[key] = update(self.storage[key], value)

    def _store(self, p: MapPayload, res) -> None:
        """The one point where a map payload's result lands in its block
        (already on the device): a device array, or a host array that is
        uploaded first."""
        if isinstance(res, np.ndarray):
            res = self._upload(res)
        self._write((p.out_base, p.out_frag.block), p.out_frag.local, res)

    def readback(self, blocks: list) -> list:
        """Host copies of device blocks, each read once: the runtime's
        one device→host read (``Runtime.gather``)."""
        host = self._jax.device_get(blocks)
        self._count(d2h=sum(x.nbytes for x in host))
        return host

    def _impl_of(self, u) -> Optional[object]:
        return self._impls.get(u.name)

    def _trace_ufunc(self, ufunc):
        """Build a jnp callable for a primitive or fused ufunc; None if a
        primitive inside has no jnp translation."""
        from repro.core.ufunc import eval_tree

        if ufunc.tree is not None:
            missing = []

            def impl(u):
                f = self._impl_of(u)
                if f is None:
                    missing.append(u.name)
                    return u.fn
                return f

            # dry-walk the tree for translatability (leaves unevaluated)
            def walk(spec):
                if spec[0] in ("leaf", "const"):
                    return
                f, subs = spec
                impl(f)
                for s in subs:
                    walk(s)

            walk(ufunc.tree)
            if missing:
                return None

            def fn(*arrays):
                return eval_tree(ufunc.tree, arrays, self._impl_of)
        else:
            f = self._impl_of(ufunc)
            if f is None:
                return None

            def fn(*arrays):
                return f(*arrays)
        # the device program's name: jit_repro_map_<ufunc>
        fn.__name__ = "repro_map_" + re.sub(r"\W+", "_", ufunc.name).strip("_")
        return fn

    @staticmethod
    def _stencil5_weight(tree) -> Optional[float]:
        """Match ``w * ((((x0+x1)+x2)+x3)+x4)`` — the fused 5-point
        stencil sweep — returning the weight, else None."""
        if not (isinstance(tree, tuple) and len(tree) == 2):
            return None
        f, subs = tree
        if getattr(f, "name", None) != "multiply" or len(subs) != 2:
            return None
        const, chain = subs
        if const[0] != "const":
            const, chain = chain, const
        if const[0] != "const":
            return None
        expect = 4
        while isinstance(chain, tuple) and len(chain) == 2 and getattr(
            chain[0], "name", None
        ) == "add":
            _, (left, right) = chain
            if right != ("leaf", expect):
                return None
            expect -= 1
            chain = left
        if chain != ("leaf", 0) or expect != 0:
            return None
        return float(const[1])

    @staticmethod
    def _tree_key(spec):
        """Structural signature of an expression tree: two independently
        built but identical fused expressions must share one jit entry
        (keying on object identity would recompile per materialize and
        pin dead closures in the cache forever)."""
        if spec is None:
            return None
        tag = spec[0]
        if tag in ("leaf", "const"):
            return spec
        f, subs = spec
        return (f.name, tuple(JaxBackend._tree_key(s) for s in subs))

    def _jnp_of(self, ufunc):
        """``(structural key, jnp callable or None)`` of a ufunc."""
        ukey = (ufunc.name, self._tree_key(ufunc.tree))
        return ukey, self._program(("jnp",) + ukey,
                                   lambda: self._trace_ufunc(ufunc))

    def _map_program(self, ufunc, sig, ukey, traced):
        """``(counter key, program)`` of a map over operands of ``sig``
        (one ``(shape, dtype, local slice or None)`` per array, None per
        constant), given the ufunc's :meth:`_jnp_of`."""
        return self._program(("map",) + ukey + (sig,),
                             lambda: self._build_map(ufunc, traced, sig))

    def _build_map(self, ufunc, traced, sig):
        cuts = [None if s is None else s[2] for s in sig]
        w = self._stencil5_weight(ufunc.tree)
        shapes = {_cut_shape(s[0], s[2]) for s in sig if s is not None}
        if (
            w is not None
            and len(sig) == 5
            and None not in sig
            and len(shapes) == 1
            and len(next(iter(shapes))) == 2
            # a halo sliver (under 8 rows or columns) fills no kernel
            # tile: XLA's fusion does as well, and compiles faster
            and min(next(iter(shapes))) >= _STENCIL5_MIN_DIM
            and all(s[1].itemsize <= 4 for s in sig)
        ):
            stencil5, interpret = self._stencil5, self.interpret

            def repro_stencil5(*blocks):
                xs = [_cut(b, c) for b, c in zip(blocks, cuts)]
                return stencil5(*xs, weight=w, interpret=interpret)

            return "n_pallas", self._jax.jit(repro_stencil5)

        def prog(*args):
            return traced(*[_cut(a, c) for a, c in zip(args, cuts)])

        prog.__name__ = traced.__name__
        return "n_jit", self._jax.jit(prog)

    def _matmul_program(self, local_a, local_b, trans_a, trans_b, dtype):
        """The matmul program for operands whose product is of ``dtype``.
        A float32 product multiplies at float32 precision (``HIGHEST``):
        at the default, a TPU runs a product over an inner dimension of 2
        or more as a single bfloat16 pass."""
        precision = (self._jax.lax.Precision.HIGHEST
                     if dtype == np.float32 else None)
        return self._program(
            ("matmul", local_a, local_b, trans_a, trans_b, precision),
            lambda: self._jax.jit(_repro_matmul(self._jnp, local_a, local_b,
                                                trans_a, trans_b, precision)),
        )

    # -- execution -------------------------------------------------------
    def execute(self, op: OperationNode) -> None:
        p = op.payload
        fid = _flush_of(op)
        kind = _KIND_COUNTERS.get(type(p))
        moved: list = []
        with _obs.span("exec.stage", fid), _combine_span(p, fid):
            key, launch = self._stages[type(p)](p, moved)
        if launch is None:  # a ufunc in it has no jnp form
            self._exec_host(p, fid, moved)
            return
        with _obs.span("exec.launch", fid), _combine_span(p, fid):
            launch()
            self._count(key, h2d=sum(moved), staged=bool(moved), kind=kind)

    # Each stage resolves a payload's refs into its program's arguments
    # and returns ``(counter key, launch)``; ``launch`` dispatches the
    # program and lands its result.

    def _stage_map(self, p: MapPayload, moved: list):
        ukey, traced = self._jnp_of(p.ufunc)
        if traced is None:
            return None, None
        ops = [self._operand(r, moved) for r in p.args]
        key, prog = self._map_program(p.ufunc, tuple(s for _, s in ops),
                                      ukey, traced)
        self._device_block((p.out_base, p.out_frag.block), moved)
        args = [v for v, _ in ops]
        return key, lambda: self._store(p, prog(*args))

    def _stage_fused(self, p: FusedMapReducePayload, moved: list):
        m = p.map
        ukey, traced = self._jnp_of(m.ufunc)
        if traced is None:
            return None, None
        ops = [self._operand(r, moved) for r in m.args]
        sig = tuple(s for _, s in ops)
        prog = self._program(
            ("fused",) + ukey + (sig, m.out_frag.shape, str(m.out_dtype),
                                 p.ufunc_name, p.axes, p.keepdims),
            lambda: self._jax.jit(_repro_map_reduce(
                self._jnp, traced, [None if s is None else s[2] for s in sig],
                m.out_frag.shape,
                self._jax.dtypes.canonicalize_dtype(m.out_dtype),
                self._reductions[p.ufunc_name], p)),
        )
        args = [v for v, _ in ops]

        def launch():
            self.scratch[p.dst_scratch] = prog(*args)

        return "n_jit", launch

    def _stage_reduce(self, p: ReducePartialPayload, moved: list):
        x, sig = self._operand(p.src, moved)
        prog = self._program(
            ("reduce", p.ufunc_name, p.axes, p.keepdims, sig[2]),
            lambda: self._jax.jit(_repro_reduce(
                self._reductions[p.ufunc_name], sig[2], p)),
        )

        def launch():
            self.scratch[p.dst_scratch] = prog(x)

        return "n_jit", launch

    def _stage_combine(self, p: CombinePayload, moved: list):
        key = (p.out_base, p.out_frag.block)
        self._device_block(key, moved)
        part = self.scratch[p.src_scratch]
        combine = None if p.init else p.ufunc_name
        return "n_jit", lambda: self._write(key, p.out_frag.local, part,
                                            combine)

    def _stage_fill(self, p: FillPayload, moved: list):
        key = (p.out_base, p.out_frag.block)
        self._device_block(key, moved)
        return "n_jit", lambda: self._write(key, p.out_frag.local, p.value)

    def _stage_matmul(self, p: MatmulPayload, moved: list):
        a, sa = self._operand(p.a, moved)
        b, sb = self._operand(p.b, moved)
        prog = self._matmul_program(sa[2], sb[2], p.trans_a, p.trans_b,
                                    self._jnp.result_type(sa[1], sb[1]))
        key = (p.out_base, p.out_frag.block)
        self._device_block(key, moved)
        combine = None if p.init else "add"
        return "n_jit", lambda: self._write(key, p.out_frag.local,
                                            prog(a, b), combine)

    def _exec_host(self, p, fid, moved: list) -> None:
        """A map (or fused map-reduce) with a ufunc that has no ``jnp``
        form: download its inputs, run NumPy, upload its result."""
        read: list = []
        with _obs.span("exec.host", fid):
            blocks = _HostCopies(self.storage, read)
            scratch = _HostCopies(self.scratch, read)
            if isinstance(p, MapPayload):
                args = [resolve_ref(r, blocks, scratch) for r in p.args]
                res = np.asarray(p.ufunc(*args))
                self._device_block((p.out_base, p.out_frag.block), moved)
                self._store(p, res)
            else:
                execute_payload(p, blocks, scratch)
                self.scratch[p.dst_scratch] = self._upload(
                    np.asarray(scratch[p.dst_scratch]))
        self._count("n_host_untranslated", h2d=sum(moved), d2h=sum(read),
                    staged=True)

    def transfer(self, p) -> None:
        """A (coalesced) transfer into scratch.  A block is immutable, so
        the block as it is at send time, with the fragment's slice, is
        the snapshot: the program that reads the scratch cuts it, and
        the transfer copies nothing.  A scratch source is passed on."""
        moved: list = []
        parts = (p.transfers if isinstance(p, CoalescedTransferPayload)
                 else (p,))
        for t in parts:
            if t.src[0] == "s":
                self.scratch[t.dst_scratch] = self.scratch[t.src[1]]
            else:
                _, bid, frag = t.src
                blk = self._device_block((bid, frag.block), moved)
                self.scratch[t.dst_scratch] = _Sent(blk, frag.local)
        self._count("n_transfer", h2d=sum(moved), staged=bool(moved))


def _flush_of(op: OperationNode):
    """The flush id of the drain ``op`` runs in, where it has one."""
    drain = getattr(op, "_drain", None)
    return None if drain is None else drain.tag


# payload kinds counted beside their path (``JaxBackend.stats()``)
_KIND_COUNTERS = {MatmulPayload: "n_matmul", CombinePayload: "n_combine"}


def _combine_span(p, fid):
    """``exec.combine`` for a combine payload, nested in its
    ``exec.stage`` and ``exec.launch``; the null span otherwise."""
    if type(p) is CombinePayload:
        return _obs.span("exec.combine", fid)
    return _obs.NO_SPAN


# The device programs, named so that a device trace names them
# (``jit_repro_<kind>``).  Each closes over static slices only.


def _repro_fill(jnp, shape, dtype):
    def repro_fill(value):
        return jnp.full(shape, value, dtype)

    return repro_fill


def _repro_update(jnp, local, combine):
    def repro_update(blk, value):
        cur = _cut(blk, local)
        if combine is not None:
            value = combine(cur, value)
        value = jnp.asarray(value).astype(blk.dtype)
        return blk.at[_slices(local)].set(value)

    return repro_update


def _repro_reduce(reduce, local, p: ReducePartialPayload):
    axes, keepdims = p.axes or None, p.keepdims

    def repro_reduce(x):
        return reduce(_cut(x, local), axis=axes, keepdims=keepdims)

    repro_reduce.__name__ = f"repro_reduce_{p.ufunc_name}"
    return repro_reduce


def _repro_map_reduce(jnp, traced, cuts, shape, dtype, reduce,
                      p: FusedMapReducePayload):
    axes, keepdims = p.axes or None, p.keepdims

    def repro_map_reduce(*args):
        res = traced(*[_cut(a, c) for a, c in zip(args, cuts)])
        # the store the unfused pair had: the map's result broadcast
        # into (and cast to) the output fragment, then reduced
        res = jnp.broadcast_to(res, shape).astype(dtype)
        return reduce(res, axis=axes, keepdims=keepdims)

    repro_map_reduce.__name__ = f"{traced.__name__}_reduce_{p.ufunc_name}"
    return repro_map_reduce


def _repro_matmul(jnp, local_a, local_b, trans_a, trans_b, precision):
    """The matmul payload's program, named ``jit_repro_matmul``."""

    def repro_matmul(x, y):
        x, y = _cut(x, local_a), _cut(y, local_b)
        return jnp.dot(x.T if trans_a else x, y.T if trans_b else y,
                       precision=precision)

    return repro_matmul


class AutoBackend(ComputeBackend):
    """Per-payload backend choice — the first registry client beyond the
    two reference backends (ROADMAP "backend autotuning").

    Blocks stay on the host.  Small block payloads stay on the eager
    NumPy interpreter (XLA dispatch + host↔device staging costs more
    than the arithmetic); payloads whose estimated per-element work
    clears ``threshold`` run on the :class:`JaxBackend`'s programs
    (including its Pallas stencil fast path): their fragments are
    uploaded, the program runs, and its result is read back into the
    host block.  The score is ``out_elements × ufunc cost`` for maps and
    output elements for matmuls — the same per-element weights the
    timeline model uses, so the choice needs no calibration run.  The
    JAX backend is built lazily on the first heavy payload and the
    choice is a pure function of the payload, so repeated drains of the
    same graph route identically (results stay deterministic across
    channel disciplines).
    """

    name = "auto"

    # default: a 128×128 float64 block of cost-4 (transcendental) work
    # clears it, a cost-1 copy/add block does not
    DEFAULT_THRESHOLD = 48_000

    def __init__(self, storage: dict, scratch: dict, threshold: int = DEFAULT_THRESHOLD):
        super().__init__(storage, scratch)
        self.threshold = threshold
        self._numpy = NumpyBackend(storage, scratch)
        self._jax: Optional[JaxBackend] = None
        self._jax_lock = threading.Lock()
        self.n_numpy = 0
        self.n_jax = 0

    def _jax_backend(self) -> JaxBackend:
        with self._jax_lock:  # workers race to the first heavy payload
            if self._jax is None:
                # its programs and counters only: it holds no blocks
                self._jax = JaxBackend({}, {})
            return self._jax

    def stats(self) -> dict:
        """Routing counts, plus the JAX backend's per-path counters once
        it has been built."""
        out = dict(n_numpy=self.n_numpy, n_jax=self.n_jax)
        if self._jax is not None:
            out.update(self._jax.stats())
        return out

    def _score(self, p) -> float:
        if isinstance(p, MapPayload):
            return p.out_frag.size * max(1.0, p.ufunc.cost)
        if isinstance(p, MatmulPayload):
            return float(p.out_frag.size)
        return 0.0  # transfers/reductions/fills: memory movement, stay eager

    def execute(self, op: OperationNode) -> None:
        if self._score(op.payload) >= self.threshold:
            self.n_jax += 1
            self._exec_staged(op)
            return
        self.n_numpy += 1
        self._numpy.execute(op)

    def _exec_staged(self, op: OperationNode) -> None:
        """A heavy map or matmul on the JAX backend's programs over host
        blocks: upload its input fragments, run, read the result back
        into the host block."""
        jb = self._jax_backend()
        p = op.payload
        fid = _flush_of(op)
        is_map = isinstance(p, MapPayload)
        if is_map:
            ukey, traced = jb._jnp_of(p.ufunc)
        if is_map and traced is None:
            with _obs.span("exec.host", fid):
                self._numpy.execute(op)
            jb._count("n_host_untranslated")
            return
        with _obs.span("exec.stage", fid):
            refs = p.args if is_map else (p.a, p.b)
            args = [resolve_ref(r, self.storage, self.scratch) for r in refs]
            dev = [a if r[0] == "c"
                   else jb._jax.device_put(np.ascontiguousarray(a))
                   for r, a in zip(refs, args)]
            uploaded = [d for r, d in zip(refs, dev) if r[0] != "c"]
            if is_map:
                sig = tuple(None if r[0] == "c" else (d.shape, d.dtype, None)
                            for r, d in zip(refs, dev))
                key, prog = jb._map_program(p.ufunc, sig, ukey, traced)
            else:
                key = "n_jit"
                prog = jb._matmul_program(
                    None, None, p.trans_a, p.trans_b,
                    jb._jnp.result_type(*(d.dtype for d in dev)))
        with _obs.span("exec.launch", fid):
            res = prog(*dev)
        with _obs.span("exec.readback", fid):
            out = np.asarray(res)
            blk = self.storage[(p.out_base, p.out_frag.block)]
            if is_map or p.init:
                blk[p.out_frag.slices] = out
            else:
                blk[p.out_frag.slices] += out
        jb._count(key, h2d=sum(d.nbytes for d in uploaded), d2h=out.nbytes,
                  staged=True, kind=None if is_map else "n_matmul")


register_backend("numpy", NumpyBackend)
register_backend("jax", JaxBackend)
register_backend("auto", AutoBackend)


def make_backend(name, storage: dict, scratch: dict) -> ComputeBackend:
    """Resolve a compute backend through the plugin registry (an
    already-built instance passes through)."""
    if isinstance(name, ComputeBackend):
        return name
    return get_backend(name)(storage, scratch)


# ---------------------------------------------------------------------------
# The asynchronous executor
# ---------------------------------------------------------------------------


class _Drain:
    """Bookkeeping for one in-flight drain on the shared pool.

    Every pending op is stamped with its owning drain at submit time
    (``op._drain``), so completion sweeps, per-drain stat accounting and
    failure cleanup can route mixed worker batches back to the right
    drain without a global registry lookup per op."""

    __slots__ = (
        "deps", "fut", "tag", "inflight", "ready_batch", "prev_hook",
        "t0", "snap", "solo", "finished", "procs",
        "comm_bytes", "n_comm_ops", "n_compute_ops", "n_handoffs",
        "n_messages",
    )

    def __init__(self, deps: DependencySystem, tag, nworkers: int):
        self.deps = deps
        self.fut = Future()
        self.tag = tag
        self.inflight = 0
        self.ready_batch: list[OperationNode] = []
        self.prev_hook = None
        self.t0 = 0.0
        self.snap: Optional[dict] = None
        # True while this drain has had the pool to itself for its whole
        # lifetime: its stats can then be the exact lifetime-delta the
        # serialized executor reported (including worker idle time)
        self.solo = True
        self.finished = False
        self.procs = [WorkerStats() for _ in range(nworkers)]
        self.comm_bytes = 0
        self.n_comm_ops = 0
        self.n_compute_ops = 0
        self.n_handoffs = 0
        self.n_messages = 0


class AsyncExecutor:
    """Drains DependencySystems on a persistent work-stealing worker
    pool + transfer channels.

    The executor is *persistent*: :meth:`submit` hands it a recorded
    graph (typically one dependency cone of a demand-driven flush) and
    returns a :class:`~repro.exec.futures.Future` that resolves — from
    the completing worker/progress thread — with that drain's
    :class:`WaitStats`.  The submitting thread keeps running (recording
    more operations) while the drain proceeds, and **multiple drains
    may be in flight concurrently**: each drain carries its own
    dependency system, in-flight counter and per-worker accounting, and
    completion sweeps route mixed batches back per drain.  The caller
    is responsible for only submitting graphs whose access footprints
    don't conflict with in-flight drains (``Runtime.flush`` joins
    conflicting tickets first — see ``repro.core.graph.cones_conflict``);
    ops *within* one submitted graph are ordered by its dependency
    system as always.  :meth:`run` is the blocking convenience
    (``submit().result()``).

    Work stealing: a worker whose queue runs dry asks :meth:`_steal_for`
    for work before parking.  Victim selection is longest-queue-first
    gated by the latency-aware threshold of arXiv 1805.01768 — steal
    only when the victim holds at least ``steal_threshold`` ops *and*
    the expected work moved (half the victim's queue × the EWMA task
    grain) exceeds ``steal_latency``, the measured cost of a steal
    round trip.  Otherwise a slow cone's tail would be diced into
    steals that cost more than they move.

    With ``batch_dispatch=True`` (set by the ``"batch"`` plan pass) the
    completion sweep groups newly-ready compute ops per worker and
    pushes each group with one lock+notify, workers drain their whole
    queue per wakeup, and a finished batch is completed through a
    single dependency-system sweep — the handoff count drops from one
    per operation to one per batch (``WaitStats.n_handoffs``)."""

    def __init__(
        self,
        nworkers: int,
        storage: dict,
        scratch: dict,
        backend: str = "numpy",
        channel: str = "async",
        latency: float = 0.0,
        progress_threads: int = 2,
        batch_dispatch: bool = False,
        steal: bool = True,
        steal_threshold: int = 4,
        steal_latency: float = 1e-4,
    ):
        self.nworkers = nworkers
        self.backend = make_backend(backend, storage, scratch)
        # a channel instance may be shared across flushes (the owner closes
        # it); a name means this executor owns the channel's lifecycle
        self._owns_channel = isinstance(channel, str)
        self.channel = make_channel(
            channel, latency=latency, progress_threads=progress_threads
        )
        self.mode = "blocking-channel" if self.channel.blocking else "async"
        self.batch_dispatch = batch_dispatch
        self.steal = steal and nworkers > 1
        self.steal_threshold = max(2, steal_threshold)
        self.steal_latency = max(0.0, steal_latency)
        # EWMA of per-op compute grain (seconds) — the τ in the 1805.01768
        # gate "move only if n·τ ≥ steal latency".  Starts at the steal
        # latency so the first steals are allowed until measured.
        self._grain_ewma = max(self.steal_latency, 1e-6)
        self.workers = [
            Worker(
                r,
                self._run_batch,
                self._record_error,
                batch=batch_dispatch,
                steal_fn=self._steal_for if self.steal else None,
            )
            for r in range(nworkers)
        ]
        self._glock = threading.Lock()  # guards drains + counters
        self._drains: dict[int, _Drain] = {}  # id(drain) -> drain
        self._anon_tags = itertools.count()
        self._error: Optional[BaseException] = None
        self._workers_started = False
        self._closed = False
        # lifetime totals (executor introspection; per-drain stats are
        # accounted per-op on each _Drain)
        self.comm_bytes = 0
        self.n_comm_ops = 0
        self.n_compute_ops = 0
        self.n_handoffs = 0

    # -- error paths -------------------------------------------------------
    def _record_error(self, exc: BaseException) -> None:
        """Pool-level failure (worker thread death, internal error): the
        pool is no longer trustworthy — poison it and fail every active
        drain."""
        with self._glock:
            if self._error is None:
                self._error = exc
            drains = list(self._drains.values())
        for d in drains:
            self._finish_drain(d, exc)

    def _fail_drain(self, drain: _Drain, exc: BaseException) -> None:
        """Per-op failure: only the owning drain dies; the pool (and any
        concurrent drains) keeps running."""
        self._finish_drain(drain, exc)

    # -- transfer execution (runs on progress threads / workers) ----------
    def _exec_comm(self, op: OperationNode) -> None:
        with _obs.span("channel.transfer", _flush_of(op)):
            self.backend.transfer(op.payload)

    # -- work stealing -----------------------------------------------------
    def _steal_for(self, thief: Worker) -> Optional[list[OperationNode]]:
        """Steal policy, run by an idle worker before parking: pick the
        longest queue holding at least ``steal_threshold`` ops, take
        half its tail (one op unbatched), but only when the expected
        work moved clears the steal-latency gate (arXiv 1805.01768)."""
        if self._closed or self._error is not None:
            return None
        victim = None
        vlen = self.steal_threshold - 1
        for w in self.workers:
            if w is thief:
                continue
            n = w.qlen()  # racy heuristic read; steal_from re-checks
            if n > vlen:
                victim, vlen = w, n
        if victim is None:
            return None
        n = max(1, vlen // 2) if self.batch_dispatch else 1
        # latency-aware gate: moving n ops pays only when their expected
        # grain amortizes the steal round trip
        if n * self._grain_ewma < self.steal_latency:
            return None
        return victim.steal_from(n) or None

    def _wake_thieves(self, loaded_ranks) -> None:
        """After a dispatch left some queue at/above the steal threshold,
        nudge parked empty-queue workers to re-run the steal policy."""
        for w in self.workers:
            if w.rank not in loaded_ranks and w.qlen() == 0:
                w.wake()

    # -- dispatch ---------------------------------------------------------
    def _count_op(self, op: OperationNode, drain: _Drain) -> None:
        """Op accounting — call with _glock held (many threads dispatch)."""
        if op.kind == COMM:
            self.n_comm_ops += 1
            self.comm_bytes += op.nbytes
            drain.n_comm_ops += 1
            drain.comm_bytes += op.nbytes
            drain.n_messages += 1  # every comm op is posted exactly once
        else:
            self.n_compute_ops += 1
            drain.n_compute_ops += 1

    def _dispatch_batch(self, ops: list[OperationNode]) -> None:
        """Route a sweep of ready ops.  COMM on the async channel is
        initiated immediately from the discovering thread in one batched
        post (aggressive initiation — invariant 2 holds even while the
        owner workers are mid-compute); everything else is grouped per
        owner and handed to the comm-first ready queues — one push per
        worker under batched dispatch, one per op otherwise."""
        if not ops:
            return
        async_comm: list[OperationNode] = []
        per_worker: dict[int, list[OperationNode]] = {}
        for op in ops:
            if op.kind == COMM and not self.channel.blocking:
                async_comm.append(op)
            else:
                per_worker.setdefault(op.procs[0] % self.nworkers, []).append(op)
        if async_comm:
            post_many = getattr(self.channel, "post_many", None)
            items = [(op, self._exec_comm) for op in async_comm]
            if post_many is not None:
                futs = post_many(items)
            else:  # channel plugin without batched posting
                futs = [self.channel.post(op, ex) for op, ex in items]
            for op, fut in zip(async_comm, futs):
                fut.add_done_callback(self._comm_callback(op))
        handoffs = 0
        heavy = False
        for rank, group in per_worker.items():
            if self.batch_dispatch:
                self.workers[rank].push_batch(group)
                handoffs += 1
            else:
                for op in group:
                    self.workers[rank].push(op)
                    handoffs += 1
            heavy = heavy or len(group) >= self.steal_threshold
        if handoffs:
            with self._glock:
                self.n_handoffs += handoffs
                for rank, group in per_worker.items():
                    seen = set()
                    for op in group:
                        d = op._drain
                        if id(d) not in seen:
                            seen.add(id(d))
                            d.n_handoffs += 1
        if self.steal and heavy:
            self._wake_thieves(set(per_worker))

    def _comm_callback(self, op: OperationNode):
        def cb(fut) -> None:
            exc = fut.exception()
            if exc is not None:
                self._fail_drain(op._drain, exc)
            else:
                self._ops_done((op,))

        return cb

    def _run_batch(self, ops: list[OperationNode], worker: Worker) -> None:
        """Execute one worker batch (comm-first order already applied by
        the pop) and complete it through a single dependency sweep.  A
        batch may mix ops from several concurrent drains; per-op stats
        are binned into each op's own drain, and a failing op kills only
        its drain — the rest of the batch still executes."""
        completed: list[OperationNode] = []
        col = _obs.CURRENT
        rank = worker.rank
        for op in ops:
            drain: _Drain = op._drain
            if drain.finished:
                continue  # drain failed elsewhere: its leftovers are void
            dstats = drain.procs[rank]
            if op.kind == COMM:  # blocking channel only: inline transfer
                t0 = time.perf_counter()  # wall: the blocking IS the waiting
                if col is not None:
                    col.wait_start(rank, "channel")
                fut = self.channel.post(op, self._exec_comm)
                try:
                    # wait for resolution: the built-in BlockingChannel
                    # resolves before post() returns, but a registered
                    # blocking transport may resolve from a delivery
                    # thread — the op must not complete before its data
                    fut.result()
                except BaseException as exc:
                    dt = time.perf_counter() - t0
                    worker.stats.comm_busy += dt
                    worker.stats.n_comm += 1
                    dstats.comm_busy += dt
                    dstats.n_comm += 1
                    if col is not None:
                        col.wait_end(rank, "channel", op.uid)
                    self._fail_drain(drain, exc)
                    continue
                dt = time.perf_counter() - t0
                worker.stats.comm_busy += dt
                worker.stats.n_comm += 1
                dstats.comm_busy += dt
                dstats.n_comm += 1
                if col is not None:
                    col.wait_end(rank, "channel", op.uid)
                completed.append(op)
                continue
            # compute is accounted in per-thread CPU time: wall durations on
            # an oversubscribed machine include GIL/scheduler preemption,
            # which would inflate "busy" exactly when contention is worst
            if col is not None:
                col.compute_start(op.uid, rank)
            t0 = time.thread_time()
            try:
                self.backend.execute(op)
            except BaseException as exc:
                if col is not None:
                    col.compute_end(op.uid, rank)
                self._fail_drain(drain, exc)
                continue
            dt = time.thread_time() - t0
            worker.stats.compute_busy += dt
            worker.stats.n_compute += 1
            dstats.compute_busy += dt
            dstats.n_compute += 1
            # unlocked EWMA: a heuristic input for the steal gate only
            self._grain_ewma += 0.2 * (dt - self._grain_ewma)
            if col is not None:
                col.compute_end(op.uid, rank)
            completed.append(op)
        if completed:
            self._ops_done(completed)

    # -- completion (worker batches and channel callbacks land here) -------
    def _ops_done(self, ops) -> None:
        # this runs on worker/progress threads (including as a future
        # done-callback): it must never raise, or the completing thread
        # dies and the drain hangs
        try:
            self._ops_done_inner(ops)
        except BaseException as internal:  # pragma: no cover - defensive
            self._record_error(internal)

    def _ops_done_inner(self, ops) -> None:
        col = _obs.CURRENT
        to_dispatch: list[OperationNode] = []
        finishing: list[tuple[_Drain, Optional[BaseException]]] = []
        with self._glock:
            groups: dict[int, list[OperationNode]] = {}
            for op in ops:
                groups.setdefault(id(op._drain), []).append(op)
            for key, dops in groups.items():
                drain = self._drains.get(key)
                if drain is None or drain.finished:
                    continue  # late completions of an already-failed drain
                deps = drain.deps
                drain.inflight -= len(dops)
                ready_pairs = [] if col is not None else None
                for op in dops:
                    # complete() returns the ops this completion made ready
                    # — the causality edge wait attribution charges along
                    made_ready = deps.complete(op)  # on_ready -> ready_batch
                    if ready_pairs is not None:
                        for nxt in made_ready:
                            ready_pairs.append((nxt.uid, op.uid))
                if ready_pairs:
                    col.ready_many(ready_pairs)
                newly = drain.ready_batch
                drain.ready_batch = []
                drain.inflight += len(newly)
                for nxt in newly:
                    self._count_op(nxt, drain)
                to_dispatch.extend(newly)
                if drain.inflight == 0:
                    finishing.append(
                        (drain, None if deps.done else self._deadlock_error(deps))
                    )
            if col is not None:
                col.counter(
                    "ops-inflight",
                    sum(d.inflight for d in self._drains.values()),
                )
        self._dispatch_batch(to_dispatch)
        for drain, exc in finishing:
            self._finish_drain(drain, exc)

    def _deadlock_error(self, deps: Optional[DependencySystem]) -> DeadlockError:
        stuck = deps.pending_ops() if deps is not None else []
        return DeadlockError(
            f"async flush stalled: {len(stuck)} operations pending, none in "
            f"flight — dependency cycle or lost completion.\nstuck operation-nodes:\n"
            + format_stuck_ops(stuck)
        )

    # -- per-drain accounting ---------------------------------------------
    def _snapshot(self) -> dict:
        return dict(
            workers=[w.stats.snapshot() for w in self.workers],
            comm_bytes=self.comm_bytes,
            n_comm_ops=self.n_comm_ops,
            n_compute_ops=self.n_compute_ops,
            n_handoffs=self.n_handoffs,
            n_posted=getattr(self.channel, "n_posted", 0),
        )

    def _stats_since(self, snap: dict, elapsed: float) -> WaitStats:
        procs = [w.stats.since(s) for w, s in zip(self.workers, snap["workers"])]
        return WaitStats(
            mode=self.mode,
            nworkers=self.nworkers,
            elapsed=elapsed,
            procs=procs,
            comm_bytes=self.comm_bytes - snap["comm_bytes"],
            n_comm_ops=self.n_comm_ops - snap["n_comm_ops"],
            n_compute_ops=self.n_compute_ops - snap["n_compute_ops"],
            seq_time=sum(p.compute_busy for p in procs),
            n_flushes=1,
            n_handoffs=self.n_handoffs - snap["n_handoffs"],
            n_messages=getattr(self.channel, "n_posted", 0) - snap["n_posted"],
        )

    def _drain_stats(self, drain: _Drain, elapsed: float) -> WaitStats:
        """Per-drain WaitStats.  A drain that had the pool to itself its
        whole lifetime reports the exact lifetime-delta the serialized
        executor reported (including worker idle time between its ops);
        an overlapped drain reports its own per-op accounting — worker
        idle/wakeups are shared-pool quantities with no meaningful
        per-drain split, so they stay zero and ``wait_fraction``
        (compute-vs-elapsed) remains well-defined per tenant."""
        if drain.solo:
            return self._stats_since(drain.snap, elapsed)
        return WaitStats(
            mode=self.mode,
            nworkers=self.nworkers,
            elapsed=elapsed,
            procs=drain.procs,
            comm_bytes=drain.comm_bytes,
            n_comm_ops=drain.n_comm_ops,
            n_compute_ops=drain.n_compute_ops,
            seq_time=sum(p.compute_busy for p in drain.procs),
            n_flushes=1,
            n_handoffs=drain.n_handoffs,
            n_messages=drain.n_messages,
        )

    def _finish_drain(
        self, drain: _Drain, exc: Optional[BaseException] = None
    ) -> None:
        """Finalize one drain exactly once: detach its graph, restore its
        hook, and resolve its future — with the measured WaitStats, or
        with ``exc``.  Runs on whichever thread completes (or kills) the
        drain's last in-flight operation."""
        with self._glock:
            if drain.finished:
                return
            drain.finished = True
            self._drains.pop(id(drain), None)
            drain.ready_batch = []
            drain.inflight = 0
        if drain.deps is not None:
            drain.deps.on_ready = drain.prev_hook
        if exc is not None:
            # a failed drain's queued-but-unexecuted leftovers must not
            # run later against state a subsequent flush re-plans
            for w in self.workers:
                w.discard(lambda op: getattr(op, "_drain", None) is drain)
        col = _obs.CURRENT
        if col is not None:
            col.drain_end(drain.tag)
        elapsed = time.perf_counter() - drain.t0
        if exc is not None:
            drain.fut.set_exception(exc)
        else:
            drain.fut.set_result(self._drain_stats(drain, elapsed))

    # -- main entry -------------------------------------------------------
    def submit(
        self,
        deps: DependencySystem,
        batch_dispatch: Optional[bool] = None,
        tag=None,
    ) -> Future:
        """Start draining ``deps`` and return a Future resolving to the
        drain's :class:`WaitStats` (or raising its failure).  Returns
        immediately; the caller keeps its thread.  May be called again
        while prior drains are in flight — concurrent drains share the
        worker pool; the caller guarantees the submitted graphs'
        access footprints don't conflict (``Runtime.flush`` serializes
        conflicting cones by joining their tickets first)."""
        return self.submit_many([(deps, tag)], batch_dispatch=batch_dispatch)[0]

    def submit_many(
        self,
        items: list,
        batch_dispatch: Optional[bool] = None,
    ) -> list:
        """Start draining several graphs — ``items`` is a list of
        ``(deps, tag)`` pairs — in ONE submission round, returning one
        Future per item (in order).  The cross-tenant cone batcher's
        entry point: registering the whole group under a single
        global-lock round, a single worker wake, and a single initial
        dispatch sweep amortizes the per-drain submission overhead that
        dominates small-cone serving workloads.

        Exactly like repeated :meth:`submit` calls otherwise; the caller
        guarantees the graphs' access footprints are mutually
        non-conflicting (the cone batcher inherits this from
        ``Runtime._join_conflicting``'s extraction-order bound).  Every
        drain submitted through a group of two or more is accounted as
        an *overlapped* drain (per-drain stats binning, never the
        solo-exact lifetime delta) — co-submitted cones share the pool
        by construction."""
        if self._closed:
            raise RuntimeError("AsyncExecutor is closed")
        if self._error is not None:
            raise self._error
        col = _obs.CURRENT
        prepared = []  # (deps, drain, pending) per item
        with self._glock:
            if batch_dispatch is not None and batch_dispatch != self.batch_dispatch:
                if self._drains:
                    raise RuntimeError(
                        "cannot switch dispatch granularity while drains "
                        "are in flight"
                    )
                self.batch_dispatch = batch_dispatch
                for w in self.workers:
                    w.set_batch(batch_dispatch)
            for deps, tag in items:
                if tag is None:
                    # drains need a distinguishable id: trace segments of
                    # concurrent drains pair begin/end events by tag
                    tag = f"anon-{next(self._anon_tags)}"
                drain = _Drain(deps, tag, self.nworkers)
                drain.prev_hook = deps.on_ready
                pending = deps.pending_ops()
                for op in pending:
                    op._drain = drain
                prepared.append((deps, drain, pending))
            if self._drains or len(prepared) > 1:
                for d in self._drains.values():
                    d.solo = False
                for _deps, drain, _p in prepared:
                    drain.solo = False
            for _deps, drain, _p in prepared:
                drain.snap = self._snapshot()
                drain.t0 = time.perf_counter()
                self._drains[id(drain)] = drain
            if not self._workers_started:
                self._workers_started = True
                for w in self.workers:
                    w.start()
        for deps, drain, pending in prepared:
            # late-bound: _ops_done swaps ready_batch for a fresh list per
            # sweep; the default-arg binding pins each drain to its hook
            deps.on_ready = lambda op, d=drain: d.ready_batch.append(op)
            if col is not None:
                col.drain_begin(drain.tag, deps.n_pending, self.nworkers)
                col.drain_ops(drain.tag, [op.uid for op in pending])
        for w in self.workers:
            w.drain_started()  # parked-between-drains time is not idle
        # initial dispatch: everything recorded ready before we attached
        to_dispatch = []
        finishing = []
        with self._glock:
            for deps, drain, _p in prepared:
                initial = []
                while True:
                    op = deps.pop_ready()
                    if op is None:
                        break
                    initial.append(op)
                    self._count_op(op, drain)
                drain.inflight += len(initial)
                to_dispatch.extend(initial)
                if not initial:
                    finishing.append(
                        (drain,
                         None if deps.done else self._deadlock_error(deps))
                    )
        for drain, exc in finishing:
            self._finish_drain(drain, exc)  # empty graph: empty stats
        if to_dispatch:
            self._dispatch_batch(to_dispatch)
        return [drain.fut for _deps, drain, _p in prepared]

    @property
    def n_active_drains(self) -> int:
        with self._glock:
            return len(self._drains)

    def run(self, deps: DependencySystem) -> WaitStats:
        """Drain ``deps`` to completion; returns the measured WaitStats
        for this flush (``submit`` + blocking wait).  The worker pool
        persists across calls until :meth:`close`."""
        return self.submit(deps).result()

    def close(self) -> None:
        """Stop the worker pool and (if owned) the channel.  Idempotent —
        a double close is a no-op.  Any still-active drain is failed
        (the owner should have joined its tickets first)."""
        if self._closed:
            return
        self._closed = True
        with self._glock:
            drains = list(self._drains.values())
        for d in drains:
            self._finish_drain(
                d, RuntimeError("AsyncExecutor closed with a drain in flight")
            )
        for w in self.workers:
            w.stop()
        if self._workers_started:
            for w in self.workers:
                w.join(timeout=5.0)
        if self._owns_channel:
            self.channel.close()


# ---------------------------------------------------------------------------
# Fig. 6 on real threads: naive BSP + two-sided rendezvous messaging
# ---------------------------------------------------------------------------


def run_rendezvous_bsp_async(
    per_proc_programs: list[list[dict]], static_check: bool = True
) -> int:
    """Execute the paper's naive evaluation (fig. 6) with real threads:
    each rank walks its own operation list in order; sends and receives
    rendezvous through a :class:`RendezvousMailbox`.

    Well-ordered schedules complete and return the number of completed
    steps.  Schedules like fig. 6's deadlock — rejected *statically at
    plan time* by the ``repro.analysis`` deadlock rule (a cycle in the
    cross-rank message-match graph, or an unmatched message) before any
    thread starts, and — for completeness with ``static_check=False`` —
    also detected structurally at runtime (all live ranks parked on
    unmatched messages).  Both paths refuse with a
    :class:`DeadlockError` listing the stuck operation-nodes.  This is
    the contrast the flush executor exists for: the *same* data movement
    expressed as one-sided transfers in a dependency graph cannot
    deadlock (§5.7.1).
    """
    if static_check:
        from repro.analysis import check

        report = check(schedule=per_proc_programs, rules=("deadlock",))
        if not report.ok:
            raise DeadlockError(
                "rendezvous-BSP schedule rejected statically at plan time "
                "(repro.analysis deadlock rule):\n"
                + "\n".join(d.message for d in report.errors)
            )
    n = len(per_proc_programs)
    mailbox = RendezvousMailbox(n)
    steps = [0] * n
    failures: list[RendezvousDeadlock] = []
    lock = threading.Lock()

    def rank_main(rank: int) -> None:
        try:
            for pc, op in enumerate(per_proc_programs[rank]):
                if op["kind"] == "compute":
                    steps[rank] += 1
                    continue
                mailbox.transact(rank, op["kind"], op["peer"], op["tag"], pc)
                steps[rank] += 1
        except RendezvousDeadlock as exc:
            with lock:
                failures.append(exc)
        finally:
            mailbox.finish(rank)

    threads = [
        threading.Thread(target=rank_main, args=(r,), name=f"bsp-rank-{r}")
        for r in range(n)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    if failures:
        stuck = failures[0].stuck
        lines = [
            f"  p{s['rank']}@step{s['step']}: {s['kind']} tag={s['tag']!r} "
            f"peer=p{s['peer']}"
            for s in stuck
        ]
        raise DeadlockError(
            "rendezvous-BSP schedule deadlocked (paper fig. 6): every live "
            "rank is parked on an unmatched two-sided message.\n"
            "stuck operation-nodes:\n" + "\n".join(lines)
        )
    return sum(steps)
