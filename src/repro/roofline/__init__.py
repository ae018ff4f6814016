"""repro.roofline — three-term roofline from compiled dry-run artifacts."""
from .analysis import (
    HW,
    PEAKS,
    CollectiveStats,
    analyze_compiled,
    collective_bytes,
    model_flops,
    peaks_for,
    roofline_terms,
)

__all__ = [
    "HW",
    "PEAKS",
    "CollectiveStats",
    "analyze_compiled",
    "collective_bytes",
    "model_flops",
    "peaks_for",
    "roofline_terms",
]
