"""Share of the window in which the server's event loop parsed, admitted, encoded, wrote, or took and delivered a batch (program spans)."""
from bench.program_spans import loop_busy_pct as read  # noqa: F401
