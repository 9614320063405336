"""Executable-cache misses (compiles or cache loads) from the window's start to its last answer; 0 when warm-up covered every shape."""
from bench.readers import executable_misses as read  # noqa: F401
