"""HBM roofline share of the order-statistics kernel in the Monte-Carlo
calls: bytes of its calls from their shapes, over 819 GB/s, over the
kernel's device time (profiler trace)."""
from bench.readers import ostat_roofline as read  # noqa: F401
