"""``python -m repro.analysis`` — the graph-lint entry point.

Runs real programs under ``verify="full"`` and gates on zero
diagnostics:

* the examples (``examples/quickstart.py``,
  ``examples/stencil_latency_hiding.py``) as subprocesses with
  ``REPRO_VERIFY=full`` exported — every flush they perform is
  plan-verified and race-checked inside the child, and a
  :class:`~repro.analysis.VerificationError` fails the child;
* the Jacobi stencil benchmark app in-process (a CI-sized problem), so
  the verifier's precision statistic (key-level cone conflicts that
  were region-level false positives) can be read off
  ``Runtime.verify_stats`` and reported.

Writes ``results/BENCH_graph_lint.json`` (consumed by
``benchmarks/make_report.py``) and exits non-zero when any program
failed verification or produced a diagnostic.

    PYTHONPATH=src python -m repro.analysis
    PYTHONPATH=src python -m repro.analysis --skip-examples   # bench only
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

REPO = os.path.abspath(
    os.path.join(os.path.dirname(__file__), os.pardir, os.pardir, os.pardir)
)

EXAMPLES = ("examples/quickstart.py", "examples/stencil_latency_hiding.py")


def lint_example(path: str, timeout: float = 900.0) -> dict:
    """Run one example with full verification enabled in its
    environment; a verification failure (or any crash) fails the
    child.

    The child may need the accelerator, which one process holds at a
    time, so this process must not have imported JAX: the in-process
    lints run only after every child has exited."""
    if "jax" in sys.modules:
        raise RuntimeError(
            "graph-lint imported JAX before starting an example; the "
            "child could not reach the accelerator"
        )
    env = dict(os.environ)
    env["REPRO_VERIFY"] = "full"
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (os.path.join(REPO, "src"), env.get("PYTHONPATH")) if p
    )
    t0 = time.perf_counter()
    proc = subprocess.run(
        [sys.executable, path],
        cwd=REPO,
        env=env,
        capture_output=True,
        text=True,
        timeout=timeout,
    )
    ok = proc.returncode == 0
    out = {
        "program": path,
        "ok": ok,
        "seconds": round(time.perf_counter() - t0, 3),
    }
    if not ok:
        tail = (proc.stderr or proc.stdout or "").strip().splitlines()[-15:]
        out["failure"] = "\n".join(tail)
    return out


def lint_stencil(n: int = 512, iters: int = 3, nprocs: int = 4) -> dict:
    """Run the stencil benchmark app in-process under verify="full" and
    return the verifier's counters (including the precision stat)."""
    sys.path.insert(0, REPO)
    import numpy as np

    from benchmarks.paper_apps import APPS
    from repro.api.config import ExecutionPolicy, RuntimeConfig
    from repro.core.engine import Runtime

    fn, defaults, _bs = APPS["jacobi_stencil"]
    config = RuntimeConfig(nprocs=nprocs, block_size=64)
    policy = ExecutionPolicy(
        flush="async", channel="async", verify="full", sync="demand"
    )
    t0 = time.perf_counter()
    with Runtime.from_config(config, policy) as rt:
        out = fn(**{**defaults, "n": n, "iters": iters})
        np.asarray(out)
        vs = rt.verify_stats
        report = rt.last_verify_report
    result = {
        "program": f"benchmarks.paper_apps:jacobi_stencil(n={n}, iters={iters})",
        "ok": vs.n_diagnostics == 0,
        "seconds": round(time.perf_counter() - t0, 3),
        "n_flushes_verified": vs.n_flushes_verified,
        "n_race_checks": vs.n_race_checks,
        "n_diagnostics": vs.n_diagnostics,
        "n_key_conflicts": vs.n_key_conflicts,
        "n_region_false_positives": vs.n_region_false_positives,
        "precision": vs.precision,
    }
    if report is not None and report.diagnostics:
        result["diagnostics"] = [str(d) for d in report.diagnostics]
    return result


def lint_overlap_probe(nprocs: int = 4) -> dict:
    """Concurrent-drain probe for the race oracle: two pairs of
    overlapping drains against one shared block.  The first pair
    conflicts only at key granularity (disjoint sub-block regions — the
    expected over-approximation), the second really overlaps, so the
    precision statistic gets a real denominator (expected 50%).

    Best-effort on counters: on a loaded box the producer drain can
    finish before the second flush checks it, so only the zero-
    diagnostics gate is asserted — the counts are reported as-is."""
    import numpy as np

    import repro

    t0 = time.perf_counter()
    with repro.runtime(nprocs=nprocs, block_size=64, flush="async",
                       channel="async", sync="demand", verify="full",
                       latency=2e-3) as rt:
        shared = repro.zeros((64,))
        a = repro.ones((256,))  # 4 blocks: rolls force halo messages
        b = repro.ones((16,))
        rt.flush()  # drain creations: the probed cones are the chains

        def slow_write(lo, hi):
            # a cross-block roll chain keeps the drain in flight long
            # enough (simulated latency per halo message) for the next
            # flush's race check to see it
            c = a
            for _ in range(30):
                c = np.roll(c, 1, axis=0) * 1.001
            shared[lo:hi] = c[lo:hi]
            return rt.flush(wait=False, targets=[shared])

        # pair 1: in-flight write of [0:16) vs read of [32:48) — same
        # block key, disjoint regions: the false positive
        t1 = slow_write(0, 16)
        y = b * 2.0 + shared[32:48]
        rt.flush(wait=False, targets=[y]).wait()
        t1.wait()
        # pair 2: in-flight write of [0:16) vs read of [8:24) — a real
        # region-level overlap
        t2 = slow_write(0, 16)
        z = b * 3.0 + shared[8:24]
        rt.flush(wait=False, targets=[z]).wait()
        t2.wait()
        np.asarray(y)
        np.asarray(z)
        vs = rt.verify_stats
        report = rt.last_verify_report
    result = {
        "program": "repro.analysis:overlap_probe",
        "ok": vs.n_diagnostics == 0,
        "seconds": round(time.perf_counter() - t0, 3),
        "n_flushes_verified": vs.n_flushes_verified,
        "n_race_checks": vs.n_race_checks,
        "n_diagnostics": vs.n_diagnostics,
        "n_key_conflicts": vs.n_key_conflicts,
        "n_region_false_positives": vs.n_region_false_positives,
        "precision": vs.precision,
    }
    if report is not None and report.diagnostics:
        result["diagnostics"] = [str(d) for d in report.diagnostics]
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m repro.analysis",
        description="graph-lint: run programs under verify='full' and "
        "gate on zero diagnostics",
    )
    ap.add_argument("--skip-examples", action="store_true",
                    help="lint only the in-process stencil benchmark")
    ap.add_argument("--n", type=int, default=512,
                    help="stencil problem size (default 512)")
    ap.add_argument("--iters", type=int, default=3,
                    help="stencil sweeps (default 3)")
    ap.add_argument("--out", default=os.path.join(REPO, "results",
                                                  "BENCH_graph_lint.json"),
                    help="result JSON path ('' disables the write)")
    args = ap.parse_args(argv)

    results = []
    if not args.skip_examples:
        for ex in EXAMPLES:
            print(f"graph-lint: {ex} (REPRO_VERIFY=full) ...", flush=True)
            r = lint_example(os.path.join(REPO, ex))
            results.append(r)
            print(f"  {'ok' if r['ok'] else 'FAILED'} "
                  f"({r['seconds']:.1f}s)")
            if not r["ok"]:
                print(r.get("failure", ""))
    print("graph-lint: jacobi_stencil benchmark (in-process) ...", flush=True)
    results.append(lint_stencil(n=args.n, iters=args.iters))
    print("graph-lint: concurrent-drain overlap probe ...", flush=True)
    results.append(lint_overlap_probe())
    for r in results[-2:]:
        print(f"  {r['program']}: {'ok' if r['ok'] else 'FAILED'} "
              f"({r['seconds']:.1f}s) — "
              f"{r['n_flushes_verified']} flushes verified, "
              f"{r['n_race_checks']} race checks, "
              f"{r['n_diagnostics']} diagnostics")
        if r["precision"] is not None:
            print(f"  cone-conflict precision: {r['precision'] * 100:.1f}% "
                  f"({r['n_region_false_positives']} of "
                  f"{r['n_key_conflicts']} key-level conflicts were "
                  f"region-level false positives)")
        for d in r.get("diagnostics", ()):
            print(f"  {d}")

    if args.out:
        os.makedirs(os.path.dirname(args.out), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump({"section": "graph-lint", "results": results}, f,
                      indent=2)
        print(f"wrote {args.out}")

    failed = [r["program"] for r in results if not r["ok"]]
    if failed:
        print(f"graph-lint FAILED for: {', '.join(failed)}")
        return 1
    print("graph-lint: all programs verified clean")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
