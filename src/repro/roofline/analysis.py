"""Roofline terms from a compiled (dry-run) XLA artifact.

    T_compute = HLO_FLOPs / (chips × peak)
    T_memory  = HLO_bytes / (chips × HBM_bw)
    T_coll    = Σ_class wire_bytes / (chips × link_bw_class)

``cost_analysis()`` supplies FLOPs / bytes-accessed.  Collective wire
bytes are NOT in cost_analysis — we parse the post-partitioning HLO text
and apply per-algorithm wire factors (ring algorithms):

    all-gather      (g-1)/g × global_output_bytes   per participating device-group
    reduce-scatter  (g-1)/g × global_input_bytes
    all-reduce      2(g-1)/g × buffer_bytes
    all-to-all      (g-1)/g × buffer_bytes
    collective-permute  full buffer_bytes

Device-groups of size 2 on the multi-pod mesh are the "pod" (DCI) axis —
they get the slower link class.
"""
from __future__ import annotations

import math
import re
from dataclasses import dataclass, field

__all__ = [
    "HW",
    "PEAKS",
    "peaks_for",
    "CollectiveStats",
    "collective_bytes",
    "analyze_compiled",
    "roofline_terms",
    "model_flops",
]


@dataclass(frozen=True)
class HW:
    """Per-chip peaks of one accelerator kind."""

    peak_flops: float  # bf16 FLOP/s
    hbm_bw: float  # B/s
    ici_bw: float  # B/s per link class (intra-pod)
    dci_bw: float  # B/s cross-pod ("pod" axis)
    hbm_bytes: float  # capacity


# Keyed by ``jax.Device.device_kind``.  TPU v5e: Google Cloud
# documentation, "TPU v5e" — 197 TFLOP/s bf16, 16 GB HBM at 819 GB/s,
# 1,600 Gbit/s of inter-chip interconnect (4 links of 50 GB/s).  The
# cross-pod rate is an assumption of the dry-run's 2-pod mesh, not a
# published figure.
PEAKS = {
    "TPU v5 lite": HW(
        peak_flops=197e12, hbm_bw=819e9, ici_bw=50e9, dci_bw=25e9,
        hbm_bytes=16e9,
    ),
}


def peaks_for(device_kind: str) -> HW:
    """The peaks of ``device_kind``; an unknown kind is an error, never
    a default."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no peaks recorded for device kind {device_kind!r} "
            f"(known: {', '.join(sorted(PEAKS))})"
        ) from None


_DTYPE_BYTES = {
    "pred": 1, "s4": 0.5, "u4": 0.5, "s8": 1, "u8": 1, "s16": 2, "u16": 2,
    "s32": 4, "u32": 4, "s64": 8, "u64": 8, "f8e4m3fn": 1, "f8e5m2": 1,
    "bf16": 2, "f16": 2, "f32": 4, "f64": 8, "c64": 8, "c128": 16,
    "token": 0, "opaque": 0,
}

# matches e.g. "bf16[16,4096,128]{2,1,0}" or "f32[]"
_SHAPE_RE = re.compile(r"\b([a-z0-9]+)\[([0-9,]*)\]")
_COLL_RE = re.compile(
    r"=\s*(?:\([^)]*\)|[a-z0-9]+\[[0-9,]*\](?:\{[^}]*\})?)\s*"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\("
)
_GROUPS_RE = re.compile(r"replica_groups=\{\{([^}]*)\}")
_GROUPS_V2_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_SRCDST_RE = re.compile(r"source_target_pairs=\{([^}]*)\}")


def _shape_bytes(text: str) -> float:
    """Sum sizes of all shapes in ``text`` (a tuple or single shape)."""
    total = 0.0
    for dt, dims in _SHAPE_RE.findall(text):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


@dataclass
class CollectiveStats:
    # wire bytes PER DEVICE, by link class
    ici_bytes: float = 0.0
    dci_bytes: float = 0.0
    by_kind: dict = field(default_factory=dict)
    n_ops: int = 0

    def add(self, kind: str, wire: float, dci: bool):
        self.n_ops += 1
        self.by_kind[kind] = self.by_kind.get(kind, 0.0) + wire
        if dci:
            self.dci_bytes += wire
        else:
            self.ici_bytes += wire


def _group_size(line: str, n_devices: int) -> int:
    m = _GROUPS_V2_RE.search(line)
    if m:
        return int(m.group(2))
    m = _GROUPS_RE.search(line)
    if m:
        return len([x for x in m.group(1).split(",") if x.strip() != ""])
    return n_devices


def collective_bytes(hlo_text: str, *, n_devices: int, pod_group_size: int = 2) -> CollectiveStats:
    """Parse post-partitioning HLO; returns per-device wire bytes."""
    stats = CollectiveStats()
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if m is None:
            continue
        kind = m.group(1)
        # operand shapes: everything inside the op's argument list; output
        # shape: before the op name.  For sizing we take the larger of the
        # two tuple sums (AG: output bigger; RS: input bigger; AR: equal).
        head, _, tail = line.partition(m.group(1))
        out_b = _shape_bytes(head)
        in_b = _shape_bytes(tail)
        buf = max(out_b, in_b)
        if kind == "collective-permute":
            pairs = _SRCDST_RE.search(line)
            wire = in_b if pairs else buf
            # permutes on the pod axis would pair across 256-boundaries;
            # treat as ICI unless the pairs jump by >= 256
            dci = False
            if pairs:
                jumps = [
                    abs(int(a) - int(b)) >= 256
                    for a, b in re.findall(r"\{(\d+),(\d+)\}", pairs.group(1))
                ]
                dci = any(jumps)
            stats.add(kind, wire, dci)
            continue
        g = _group_size(line, n_devices)
        if g <= 1:
            continue
        frac = (g - 1) / g
        # HLO shapes here are PER-DEVICE (post-partitioning).  Ring wire
        # bytes per device: AG sends the local shard g-1 times = (g-1) ×
        # in_b = frac × out_b (out = g × in); RS symmetric; AR = AG+RS.
        if kind == "all-gather":
            wire = frac * out_b
        elif kind == "reduce-scatter":
            wire = frac * in_b
        elif kind == "all-to-all":
            wire = frac * buf
        else:  # all-reduce
            wire = 2 * frac * in_b
        dci = g == pod_group_size and n_devices > 256
        stats.add(kind, wire, dci)
    return stats


def model_flops(cfg, shape) -> float:
    """6·N·D (train) / 2·N·D (inference) per step, N = active params."""
    n_active = cfg.param_count(active_only=True)
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n_active * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n_active * tokens
    tokens = shape.global_batch  # decode: one token per sequence
    return 2.0 * n_active * tokens


def analyze_compiled(compiled, *, n_devices: int) -> dict:
    """Extract flops / bytes / collective wire bytes from a compiled
    executable.  cost_analysis flops are whole-program (all devices)."""
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0]
    flops = float(cost.get("flops", 0.0))
    bytes_accessed = float(cost.get("bytes accessed", 0.0))
    try:
        hlo = compiled.as_text()
    except Exception:
        hlo = ""
    coll = collective_bytes(hlo, n_devices=n_devices)
    mem = {}
    try:
        ma = compiled.memory_analysis()
        for k in (
            "argument_size_in_bytes",
            "output_size_in_bytes",
            "temp_size_in_bytes",
            "generated_code_size_in_bytes",
        ):
            mem[k] = getattr(ma, k, None)
    except Exception:
        pass
    return {
        "hlo_flops": flops,
        "hlo_bytes": bytes_accessed,
        "coll_ici_bytes": coll.ici_bytes,
        "coll_dci_bytes": coll.dci_bytes,
        "coll_by_kind": coll.by_kind,
        "coll_ops": coll.n_ops,
        "memory": mem,
    }


def roofline_terms(analysis: dict, *, n_devices: int, hw: HW) -> dict:
    """The three terms in seconds + the dominant bottleneck.

    ``cost_analysis()`` on the compiled artifact reports the PER-PARTITION
    (per-device) program — verified against 6·N·D in EXPERIMENTS.md — so
    each term divides by the per-chip rate directly, NOT by chips again.
    Collective wire bytes from the parser are likewise per-device.
    """
    t_compute = analysis["hlo_flops"] / hw.peak_flops
    t_memory = analysis["hlo_bytes"] / hw.hbm_bw
    t_coll = (
        analysis["coll_ici_bytes"] / hw.ici_bw
        + analysis["coll_dci_bytes"] / hw.dci_bw
    )
    terms = {"compute": t_compute, "memory": t_memory, "collective": t_coll}
    dom = max(terms, key=terms.get)
    bound = max(terms.values())
    frac = t_compute / bound if bound > 0 else 0.0
    return {
        **{f"t_{k}": v for k, v in terms.items()},
        "dominant": dom,
        "roofline_fraction": frac,  # compute-term share of the bound
    }
