"""repro: Distributed quasi-Newton robust estimation under differential
privacy (Wang, Zhu & Zhu 2024) as a production JAX framework."""

__version__ = "1.0.0"
