"""Property-based plan-stage invariant: any registered pass pipeline
preserves the total order of conflicting accesses, so planned graphs
stay bit-identical to the unplanned simulator on random programs.

Random programs mix fills, strided slice writes, elementwise maps with
cross-block transfers, in-place updates, and reductions over dead
temporaries — the exact shapes the coalesce/fuse rewrites target.

The demand-driven readback surface adds a second axis: under
``sync="demand"`` every readback extracts and drains only the
dependency cone of its base, so the *forcing order* of multiple cones
partitions the recorded graph differently on every run.  The second
property below randomizes that order and checks every pass pipeline ×
sync mode combination against the unplanned barrier simulator.
"""
import random

import numpy as np
import pytest

pytest.importorskip("hypothesis")  # property tests need the dev extra
from hypothesis import assume, given, settings, strategies as st

import repro

SHAPE = (8, 6)
N_ARRAYS = 3

# one program step: (kind, *params); indexes are taken modulo the pool
_step = st.one_of(
    st.tuples(st.just("fill"), st.integers(0, 9), st.integers(0, 7),
              st.integers(0, 5), st.floats(-4, 4, allow_nan=False)),
    st.tuples(st.just("binop"), st.integers(0, 9), st.integers(0, 9),
              st.sampled_from(["add", "mul", "max"])),
    st.tuples(st.just("setslice"), st.integers(0, 9), st.integers(0, 9),
              st.integers(0, 7)),
    st.tuples(st.just("iadd"), st.integers(0, 9), st.integers(0, 9)),
    st.tuples(st.just("sumexpr"), st.integers(0, 9), st.integers(0, 9),
              st.integers(0, 1)),
    st.tuples(st.just("reduce"), st.integers(0, 9), st.integers(0, 1)),
)
programs = st.lists(_step, min_size=1, max_size=10)

_BINOPS = {
    "add": lambda a, b: a + b,
    "mul": lambda a, b: a * b,
}


def _exec_program(prog, force_seed=None):
    """Interpret one program inside the *current* runtime and force
    every array/output (in a seed-shuffled cone order when asked),
    returning the gathered ndarrays."""
    from repro.core import darray as dnp

    arrs = [
        dnp.array(np.arange(48.0).reshape(SHAPE) * (i + 1) - 20.0)
        for i in range(N_ARRAYS)
    ]
    outs = []
    for step in prog:
        kind = step[0]
        if kind == "fill":
            _, d, r0, c0, v = step
            dst = arrs[d % len(arrs)]
            dst[r0 % SHAPE[0]:, c0 % SHAPE[1]:] = float(v)
        elif kind == "binop":
            _, a, b, opname = step
            x, y = arrs[a % len(arrs)], arrs[b % len(arrs)]
            if opname == "max":
                arrs.append(dnp.maximum(x, y))
            else:
                arrs.append(_BINOPS[opname](x, y))
        elif kind == "setslice":
            _, d, s, r0 = step
            dst, src = arrs[d % len(arrs)], arrs[s % len(arrs)]
            lo = r0 % SHAPE[0]
            dst[lo:, :] = src[lo:, :]
        elif kind == "iadd":
            _, d, s = step
            if d % len(arrs) != s % len(arrs):
                arrs[d % len(arrs)] += arrs[s % len(arrs)]
        elif kind == "sumexpr":
            _, a, b, ax = step
            x, y = arrs[a % len(arrs)], arrs[b % len(arrs)]
            outs.append((x * y).sum(axis=ax))  # dead temp -> fuse target
        elif kind == "reduce":
            _, a, ax = step
            outs.append(arrs[a % len(arrs)].sum(axis=ax))
    everything = list(arrs) + list(outs)
    results = [None] * len(everything)
    order = list(range(len(everything)))
    if force_seed is not None:
        # randomized forcing order: each readback extracts + drains
        # one dependency cone; the cones partition the graph
        # differently for every permutation
        random.Random(force_seed).shuffle(order)
    for i in order:
        results[i] = np.asarray(everything[i]).copy()
    return results


def _records_an_op(prog) -> bool:
    """Whether ``_exec_program`` records at least one operation: every
    step does except an ``iadd`` whose operands resolve to one array
    (the pool holds ``N_ARRAYS`` until the first recording step)."""
    return any(step[0] != "iadd" or step[1] % N_ARRAYS != step[2] % N_ARRAYS
               for step in prog)


def _run(prog, passes, sync="auto", force_seed=None, verify="off",
         verify_stats_out=None):
    with repro.runtime(nprocs=4, block_size=3, passes=passes, sync=sync,
                       verify=verify) as _rt:
        if verify_stats_out is not None:
            verify_stats_out.append(_rt.verify_stats)
        return _exec_program(prog, force_seed=force_seed)


@settings(max_examples=20, deadline=None)
@given(prog=programs)
def test_passes_bit_identical_to_unplanned_simulator(prog):
    baseline = _run(prog, passes=())
    for pipeline in (("coalesce",), ("fuse",), ("coalesce", "fuse")):
        got = _run(prog, passes=pipeline)
        assert len(got) == len(baseline)
        for ref, out in zip(baseline, got):
            np.testing.assert_array_equal(ref, out, err_msg=f"{pipeline}")


# per-tenant chain step for the concurrent-cone property: elementwise
# ops plus rolls (the roll forces cross-block halo transfers, so the
# overlapping drains really do share the channel and the worker pool)
_tenant_op = st.one_of(
    st.tuples(st.just("mul"), st.floats(-2, 2, allow_nan=False)),
    st.tuples(st.just("add"), st.floats(-2, 2, allow_nan=False)),
    st.tuples(st.just("roll"), st.integers(-3, 3), st.integers(0, 1)),
)
tenant_programs = st.lists(
    st.lists(_tenant_op, min_size=1, max_size=5), min_size=2, max_size=4
)


def _apply_chain(x, prog):
    """Run one tenant's op chain on ``x`` — a NumPy ndarray or a
    DistArray (np.roll dispatches through __array_function__)."""
    for step in prog:
        if step[0] == "mul":
            x = x * step[1]
        elif step[0] == "add":
            x = x + step[1]
        else:
            x = np.roll(x, step[1], axis=step[2])
    return x


@settings(max_examples=8, deadline=None)
@given(progs=tenant_programs, seed=st.integers(0, 2**16))
def test_concurrent_disjoint_cones_bit_identical_to_barrier(progs, seed):
    """Serving-runtime property: each tenant's chain hangs off its own
    base array, so the cones are pairwise disjoint; submitting every
    cone via ``flush(wait=False)`` in a random order — all in flight
    before any is awaited — must be bit-identical to one barrier flush
    of the same graph and to the NumPy closed form, for both the empty
    and the full pass pipeline."""
    from repro.core import darray as dnp

    hosts = [
        np.arange(48.0).reshape(SHAPE) * (i + 1) - 20.0
        for i in range(len(progs))
    ]
    expected = [_apply_chain(h, p) for h, p in zip(hosts, progs)]
    for passes in ((), ("coalesce", "fuse", "batch")):
        # concurrent leg: every cone submitted before any wait
        with repro.runtime(nprocs=4, block_size=3, passes=passes,
                           flush="async", sync="demand",
                           latency=1e-3) as rt:
            outs = [_apply_chain(dnp.array(h), p)
                    for h, p in zip(hosts, progs)]
            order = list(range(len(outs)))
            random.Random(seed).shuffle(order)
            tickets = [(i, rt.flush(wait=False, targets=[outs[i]]))
                       for i in order]
            for _, t in tickets:
                t.wait()
            got = [np.asarray(o).copy() for o in outs]
        # barrier leg: the same graph, one whole-graph drain
        with repro.runtime(nprocs=4, block_size=3, passes=passes,
                           flush="async", sync="demand",
                           latency=1e-3) as rt:
            outs = [_apply_chain(dnp.array(h), p)
                    for h, p in zip(hosts, progs)]
            rt.flush()
            got_barrier = [np.asarray(o).copy() for o in outs]
        for ref, c, b in zip(expected, got, got_barrier):
            np.testing.assert_array_equal(
                c, ref, err_msg=f"concurrent diverged, passes={passes}"
            )
            np.testing.assert_array_equal(
                b, ref, err_msg=f"barrier diverged, passes={passes}"
            )


@settings(max_examples=12, deadline=None)
@given(prog=programs, seed=st.integers(0, 2**16))
def test_builtin_pipelines_verify_clean(prog, seed):
    """Static-verifier property: random programs × every built-in pass
    pipeline × sync modes produce ZERO diagnostics under
    ``verify="full"`` — no VerificationError, nothing collected.  Every
    diagnostic on a real program is a pass bug, not noise."""
    assume(_records_an_op(prog))  # else there is no flush to verify
    for pipeline in (("coalesce",), ("fuse",), ("coalesce", "fuse")):
        for sync in ("barrier", "demand"):
            sink = []
            _run(prog, passes=pipeline, sync=sync, force_seed=seed,
                 verify="full", verify_stats_out=sink)
            vs = sink[0]
            assert vs.n_diagnostics == 0, (
                f"passes={pipeline} sync={sync}: {vs}"
            )
            assert vs.n_flushes_verified >= 1


@settings(max_examples=12, deadline=None)
@given(prog=programs, seed=st.integers(0, 2**16))
def test_mutated_pipeline_always_flagged(prog, seed):
    """The complement: a seeded dependence-inverting mutant appended to
    the pipeline is *always* caught (the program is salted with one
    guaranteed conflicting write pair, so every run has an inversion to
    find)."""
    from repro.analysis import VerificationError
    from repro.api.registry import PASSES, register_pass

    def evil_reverse(ctx):
        if len(ctx.ops) > 1:
            ctx.ops = list(reversed(ctx.ops))
            ctx.dirty = True

    register_pass("evil-reverse-prop", evil_reverse, overwrite=True)
    try:
        salted = [("fill", 0, 0, 0, 1.0), ("iadd", 0, 1)] + list(prog)
        with pytest.raises(VerificationError):
            _run(salted, passes=("evil-reverse-prop",), sync="barrier",
                 verify="plan")
    finally:
        PASSES.unregister("evil-reverse-prop")


@settings(max_examples=15, deadline=None)
@given(prog=programs, seed=st.integers(0, 2**16))
def test_demand_cone_forcing_order_bit_identical(prog, seed):
    """Acceptance gate: every pass pipeline × sync mode combination is
    bit-identical to the unplanned barrier simulator, with the cones
    forced in a random order under sync="demand"."""
    baseline = _run(prog, passes=())
    for pipeline in ((), ("coalesce",), ("fuse",), ("coalesce", "fuse")):
        for sync in ("barrier", "demand"):
            got = _run(prog, passes=pipeline, sync=sync, force_seed=seed)
            assert len(got) == len(baseline)
            for ref, out in zip(baseline, got):
                np.testing.assert_array_equal(
                    ref, out, err_msg=f"passes={pipeline} sync={sync}"
                )


@settings(max_examples=10, deadline=None)
@given(prog=programs, seed=st.integers(0, 2**16))
def test_plan_cache_hits_bit_identical_to_cold_plans(prog, seed):
    """Plan-shape-cache property: running a random program twice inside
    one runtime (same forcing order, so the second repetition's cones
    are renamings of the first's) must hit the cache and stay
    bit-identical to the cache-off run and to the unplanned simulator —
    a replayed recipe is the *same plan*, re-targeted."""
    assume(_records_an_op(prog))  # else there is no cone to cache
    baseline = _run(prog, passes=())
    for pipeline in (("coalesce",), ("coalesce", "fuse")):
        legs = {}
        for cache_on in (False, True):
            with repro.runtime(nprocs=4, block_size=3, passes=pipeline,
                               sync="demand", plan_cache=cache_on) as rt:
                reps = [_exec_program(prog, force_seed=seed)
                        for _ in range(2)]
                if cache_on:
                    assert rt._plan_cache is not None
                    # every cone of rep 2 is a renaming of a rep-1 cone
                    assert rt._plan_cache.hits > 0, repr(rt._plan_cache)
            legs[cache_on] = reps
        for cache_on, reps in legs.items():
            for rep in reps:
                assert len(rep) == len(baseline)
                for ref, out in zip(baseline, rep):
                    np.testing.assert_array_equal(
                        ref, out,
                        err_msg=f"passes={pipeline} cache={cache_on}",
                    )
