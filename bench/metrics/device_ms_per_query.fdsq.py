"""Device busy milliseconds in the traced window per query answered in it."""
from bench.readers import device_ms_per_query as read  # noqa: F401
