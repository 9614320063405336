"""Mean milliseconds from one dispatch's end on the worker thread to the next one's start, over the window's FQ-SD dispatches that had requests queued at that end (program spans)."""
from bench.program_spans import dispatch_lag_ms as read  # noqa: F401
