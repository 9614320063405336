"""Roofline analysis from compiled dry-run artifacts (no real hardware)."""
from repro.roofline.hw import PEAKS, TPU_V5E, peaks_for
from repro.roofline.analysis import analyze_compiled, collective_bytes_from_hlo, roofline_terms
