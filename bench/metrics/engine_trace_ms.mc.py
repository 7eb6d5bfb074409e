"""Host ms per ``run_monte_carlo`` call spent tracing Algorithm 1's
engine: the ``repro.protocol.trace`` spans of the window over its calls
(program span)."""
from bench.scopes import engine_trace_ms as read  # noqa: F401
