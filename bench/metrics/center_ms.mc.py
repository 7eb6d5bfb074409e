"""Device ms per ``run_monte_carlo`` call under the name scope
``alg1.center``: the center's aggregations, the order-statistics kernel
among them, its estimators and updates (profiler trace, op metadata)."""
from bench.scopes import center_ms as read  # noqa: F401
