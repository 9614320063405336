"""Answered queries per dispatch of the collection's ContinuousBatcher over the window."""
from bench.readers import rows_per_dispatch as read  # noqa: F401
