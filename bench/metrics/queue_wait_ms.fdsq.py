"""Mean milliseconds a request of the window's FD-SQ dispatches waited in the batcher's queue (program spans)."""
from bench.program_spans import queue_wait_ms as read  # noqa: F401
