"""Device ms per ``run_monte_carlo`` call under the name scope
``alg1.local``: the machines' Newton solves, gradients, Hessian solves
and gradient differences (profiler trace, op metadata)."""
from bench.scopes import local_ms as read  # noqa: F401
