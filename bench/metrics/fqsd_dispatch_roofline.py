"""Least time of the window's dispatches at the HBM and bf16 peaks over the device's busy time, which is the whole dispatch: scan and top-k merge."""
from bench.readers import dispatch_roofline as read  # noqa: F401
