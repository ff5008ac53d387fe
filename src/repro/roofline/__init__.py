"""Roofline analysis from compiled dry-run artifacts (DESIGN.md §6)."""

from repro.roofline import analysis, hlo
from repro.roofline.analysis import (HBM_BW, ICI_BW, PEAK_FLOPS,
                                     RooflineReport, build_report,
                                     model_flops, suggestion)
from repro.roofline.hlo import analyze_hlo, parse_computations

__all__ = ["analysis", "hlo", "HBM_BW", "ICI_BW", "PEAK_FLOPS",
           "RooflineReport", "build_report", "model_flops", "suggestion",
           "analyze_hlo", "parse_computations"]
