"""Host milliseconds of request decode and response encode per answered query."""
from bench.readers import codec_ms_per_query as read  # noqa: F401
