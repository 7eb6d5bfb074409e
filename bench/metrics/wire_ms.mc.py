"""Device ms per ``run_monte_carlo`` call under the name scope
``alg1.wire``: DP noise and Byzantine corruption on the wire (profiler
trace, op metadata)."""
from bench.scopes import wire_ms as read  # noqa: F401
