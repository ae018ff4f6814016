import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

The two lines above MUST run before any other import (jax locks the
device count on first init).  512 placeholder host devices back both the
single-pod 16×16 mesh (first 256) and the 2×16×16 multi-pod mesh.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch yi-34b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod] [--out results/dryrun]

Per cell it records: compile success, memory_analysis, cost_analysis,
and the parsed collective wire bytes — the roofline table reads these
JSON artifacts (single-pod only; the multi-pod pass proves the "pod"
axis shards).
"""
import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
import time  # noqa: E402
import traceback  # noqa: E402
from pathlib import Path  # noqa: E402

import jax  # noqa: E402

from repro.configs import SHAPES, all_arch_ids  # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.launch.sharding import shardings  # noqa: E402
from repro.launch.steps import cell, skip_reason  # noqa: E402
from repro.roofline.analysis import analyze_compiled, model_flops, peaks_for, roofline_terms  # noqa: E402

# the 16x16 and 2x16x16 meshes stand for v5e pods; the placeholder host
# devices carry no device kind of their own
TARGET_HW = peaks_for("TPU v5 lite")

DEFAULT_OUT = Path("results/dryrun")


def run_cell(arch: str, shape_name: str, *, multi_pod: bool, out_dir: Path,
             overrides: dict | None = None, tag: str = "") -> dict:
    t0 = time.time()
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    rec = {
        "arch": arch,
        "shape": shape_name,
        "mesh": mesh_name,
        "tag": tag,
        "status": "unknown",
    }
    reason = skip_reason(arch, shape_name)
    if reason:
        rec["status"] = "skipped"
        rec["reason"] = reason
        return _save(rec, out_dir)
    try:
        mesh = make_production_mesh(multi_pod=multi_pod)
        n_dev = mesh.size
        c = cell(arch, shape_name, mesh, **(overrides or {}))
        in_sh = shardings(c.in_shardings, mesh)
        out_sh = shardings(c.out_shardings, mesh)
        with mesh:
            lowered = jax.jit(c.fn, in_shardings=in_sh, out_shardings=out_sh).lower(*c.args)
            t_lower = time.time() - t0
            compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower
        analysis = analyze_compiled(compiled, n_devices=n_dev)
        mf = model_flops(c.cfg, c.shape)
        terms = roofline_terms(analysis, n_devices=n_dev, hw=TARGET_HW)
        rec.update(
            status="ok",
            kind=c.kind,
            n_devices=n_dev,
            lower_s=round(t_lower, 2),
            compile_s=round(t_compile, 2),
            model_flops=mf,
            # hlo_flops are per-device; useful-compute ratio compares the
            # whole-job model FLOPs against chips × per-device HLO FLOPs
            useful_ratio=(mf / (analysis["hlo_flops"] * n_dev)) if analysis["hlo_flops"] else None,
            **analysis,
            **terms,
        )
        try:
            print(compiled.memory_analysis())
        except Exception:
            pass
        ca = compiled.cost_analysis()
        print({k: ca[k] for k in ("flops", "bytes accessed") if k in ca})
    except Exception as e:
        rec["status"] = "fail"
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-4000:]
    rec["wall_s"] = round(time.time() - t0, 2)
    return _save(rec, out_dir)


def _probe_pattern(cfg):
    """Two shallow probe configs (k1, k2 layers) such that the full cost
    is linear: F(L) = F(k2) + (L-k2)/(k2-k1) · (F(k2)-F(k1)).

    Periodic patterns probe 1 and 2 periods; prefix+tail patterns (e.g.
    deepseek 'D'+'E'*26) probe prefix+1 and prefix+2 tail units.
    """
    pat = cfg.pattern
    L = len(pat)
    for p in range(1, L + 1):
        if L % p == 0 and pat == pat[:p] * (L // p):
            break
    if L // p > 1:
        k1, k2 = p, 2 * p
    else:
        # prefix of runs + homogeneous tail: unit = one tail layer
        tail = pat[-1]
        t0 = L
        while t0 > 0 and pat[t0 - 1] == tail:
            t0 -= 1
        k1, k2 = t0 + 1, t0 + 2
    assert (L - k2) % (k2 - k1) == 0, (pat, k1, k2)
    return k1, k2


def run_cost_probe(arch: str, shape_name: str, *, multi_pod: bool, out_dir: Path,
                   overrides: dict | None = None, tag: str = "cost") -> dict:
    """Extrapolated true-cost record (tag='cost').  XLA counts while-loop
    bodies once, so the scanned main pass under-reports FLOPs; here two
    SHALLOW fully-unrolled probes are compiled and costs extrapolated
    linearly in depth — every number still comes from compiled artifacts.
    """
    import time as _t

    t0 = _t.time()
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
           "tag": tag, "status": "unknown"}
    reason = skip_reason(arch, shape_name)
    if reason:
        rec["status"] = "skipped"
        rec["reason"] = reason
        return _save(rec, out_dir)
    try:
        mesh = make_production_mesh(multi_pod=multi_pod)
        n_dev = mesh.size
        base_cfg = cell(arch, shape_name, mesh).cfg  # for L and pattern
        k1, k2 = _probe_pattern(base_cfg)
        L = base_cfg.n_layers
        probes = []
        for k in (k1, k2):
            ov = dict(overrides or {})
            ov.update(
                n_layers=k, layer_pattern=base_cfg.pattern[:k],
                n_enc_layers=(max(1, base_cfg.n_enc_layers * k // L)
                              if base_cfg.enc_dec else 0),
                unroll_scans=True, scan_layers=False, microbatches=1,
            )
            c = cell(arch, shape_name, mesh, **ov)
            in_sh = shardings(c.in_shardings, mesh)
            out_sh = shardings(c.out_shardings, mesh)
            with mesh:
                compiled = jax.jit(c.fn, in_shardings=in_sh,
                                   out_shardings=out_sh).lower(*c.args).compile()
            probes.append(analyze_compiled(compiled, n_devices=n_dev))
        a1, a2 = probes
        scale = (L - k2) / (k2 - k1)

        def extrap(key):
            return a2[key] + scale * (a2[key] - a1[key])

        analysis = {
            "hlo_flops": extrap("hlo_flops"),
            "hlo_bytes": extrap("hlo_bytes"),
            "coll_ici_bytes": extrap("coll_ici_bytes"),
            "coll_dci_bytes": extrap("coll_dci_bytes"),
            "coll_by_kind": {
                kk: a2["coll_by_kind"].get(kk, 0.0)
                + scale * (a2["coll_by_kind"].get(kk, 0.0) - a1["coll_by_kind"].get(kk, 0.0))
                for kk in set(a1["coll_by_kind"]) | set(a2["coll_by_kind"])
            },
            "coll_ops": int(extrap("coll_ops")),
            "memory": a2["memory"],
            "probe_layers": [k1, k2],
        }
        c_full = cell(arch, shape_name, mesh, **(overrides or {}))
        mf = model_flops(c_full.cfg, c_full.shape)
        terms = roofline_terms(analysis, n_devices=n_dev, hw=TARGET_HW)
        rec.update(
            status="ok", kind=c_full.kind, n_devices=n_dev,
            model_flops=mf,
            useful_ratio=mf / (analysis["hlo_flops"] * n_dev) if analysis["hlo_flops"] else None,
            **analysis, **terms,
        )
    except Exception as e:
        rec["status"] = "fail"
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-4000:]
    rec["wall_s"] = round(_t.time() - t0, 2)
    return _save(rec, out_dir)


def _save(rec: dict, out_dir: Path) -> dict:
    out_dir.mkdir(parents=True, exist_ok=True)
    tag = f"_{rec['tag']}" if rec.get("tag") else ""
    name = f"{rec['arch']}_{rec['shape']}_{rec['mesh']}{tag}.json"
    (out_dir / name).write_text(json.dumps(rec, indent=1, default=str))
    status = rec["status"]
    extra = ""
    if status == "ok":
        extra = (f" dom={rec['dominant']} frac={rec['roofline_fraction']:.3f}"
                 f" wall={rec.get('compile_s', rec.get('wall_s', 0)):.0f}s")
    elif status == "fail":
        extra = " " + rec["error"][:140]
    print(f"[dryrun] {rec['arch']:22s} {rec['shape']:12s} {rec['mesh']:10s} {status}{extra}", flush=True)
    return rec


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=list(SHAPES) + [None])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--out", default=str(DEFAULT_OUT))
    ap.add_argument("--override", action="append", default=[],
                    help="cfg override key=value (int/float/str)")
    ap.add_argument("--tag", default="")
    ap.add_argument("--cost-pass", action="store_true",
                    help="unroll every scan so cost_analysis counts true "
                         "FLOPs (XLA counts while bodies once); tags the "
                         "record 'cost'")
    args = ap.parse_args(argv)

    overrides = {}
    for ov in args.override:
        k, _, v = ov.partition("=")
        try:
            overrides[k] = int(v)
        except ValueError:
            try:
                overrides[k] = float(v)
            except ValueError:
                overrides[k] = {"true": True, "false": False, "none": None}.get(v.lower(), v)

    out_dir = Path(args.out)
    archs = all_arch_ids() if (args.all or args.arch is None) else [args.arch]
    shapes = list(SHAPES) if (args.all or args.shape is None) else [args.shape]
    meshes = [False, True] if args.both_meshes else [args.multi_pod]

    n_fail = 0
    for mp in meshes:
        for arch in archs:
            for shape in shapes:
                if args.cost_pass:
                    rec = run_cost_probe(arch, shape, multi_pod=mp,
                                         out_dir=out_dir, overrides=overrides,
                                         tag=args.tag or "cost")
                else:
                    rec = run_cell(arch, shape, multi_pod=mp, out_dir=out_dir,
                                   overrides=overrides, tag=args.tag)
                n_fail += rec["status"] == "fail"
    sys.exit(1 if n_fail else 0)


if __name__ == "__main__":
    main()
