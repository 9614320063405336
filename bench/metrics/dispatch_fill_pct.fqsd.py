"""Rows the window's FQ-SD dispatches served over the rows they ran, padding to a power of two included (program counters)."""
from bench.program_spans import dispatch_fill_pct as read  # noqa: F401
