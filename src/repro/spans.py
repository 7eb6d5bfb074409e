"""Names of the program's profiler spans and device scopes.

Every name lives here, so the program and what reads a profile of it
(``bench/``) spell it once. Nothing is switched on: with no profiler
running a host span is a flag check, and a name scope changes only the
op metadata of the compiled program.

- :func:`span` is a host span (``jax.profiler.TraceAnnotation``), on the
  profiler's clock, the same as the device's events.
- :func:`scope` is a ``jax.named_scope`` for traced code: the ops traced
  under it carry the name in their metadata (``op_name``), so device
  time can be put down to a layer of Algorithm 1. It also opens a host
  span of the same name, which runs at trace time only.
"""
from __future__ import annotations

import contextlib

import jax

#: one engine call of ``DPQNProtocol``: trace, lowering, persistent-cache
#: fetch and launch
PROTOCOL_DISPATCH = "repro.protocol.dispatch"
#: one trace of ``protocol_rounds``; opened only while JAX traces
PROTOCOL_TRACE = "repro.protocol.trace"
#: ``DPQNProtocol`` building the jitted engines of one attack mode; opened
#: only when it has none for that mode yet
PROTOCOL_BUILD = "repro.protocol.build"

#: Algorithm 1's machines' local work: Newton solves, gradients, Hessian
#: solves, gradient differences
ALG1_LOCAL = "alg1.local"
#: DP noise on the wire and Byzantine corruption
ALG1_WIRE = "alg1.wire"
#: the center: aggregation and its own estimators and updates
ALG1_CENTER = "alg1.center"
ALG1_SCOPES = (ALG1_LOCAL, ALG1_WIRE, ALG1_CENTER)


def span(name: str):
    """A host span named ``name``."""
    return jax.profiler.TraceAnnotation(name)


@contextlib.contextmanager
def scope(name: str):
    """A device name scope named ``name``, with a host span of the same
    name around its tracing."""
    with jax.named_scope(name), jax.profiler.TraceAnnotation(name):
        yield
