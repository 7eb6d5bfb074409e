"""Host ms per ``run_monte_carlo`` call in the engine's dispatch besides
its trace: ``repro.protocol.dispatch`` less ``repro.protocol.trace``
(lowering, persistent-cache fetch, launch), over the calls (program
span)."""
from bench.scopes import engine_lower_ms as read  # noqa: F401
