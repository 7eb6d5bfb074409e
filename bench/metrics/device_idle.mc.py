"""Share of the Monte-Carlo window in which no operation ran on the
device (profiler trace)."""
from bench.readers import idle_percent as read  # noqa: F401
