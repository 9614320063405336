"""Mean host milliseconds of a window FD-SQ dispatch on the worker thread, its wait for the device left out: stack and pad, plan and launch, accounting (program spans)."""
from bench.program_spans import dispatch_host_ms as read  # noqa: F401
