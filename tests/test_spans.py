"""The program's profiler spans and device scopes (repro.spans): the host
spans of one engine call in a CPU profile, and the name scopes of
Algorithm 1's layers in the compiled engine's op metadata."""
import glob
import re

import jax
import jax.numpy as jnp
import pytest

from repro import spans
from repro.configs.base import ProtocolConfig
from repro.core import DPQNProtocol, get_problem
from repro.data.synthetic import make_shards

M, N, REPS = 10, 200, 8


@pytest.fixture(scope="module")
def shards():
    return make_shards(jax.random.PRNGKey(0), "logistic", M, N, 10)


def _cfg(**kw):
    return ProtocolConfig(eps=10.0, delta=0.05, **kw)


def _host_events(log_dir):
    """``(name, start_ns, end_ns)`` of every host event in the profile."""
    from jax.profiler import ProfileData
    path = sorted(glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True))[-1]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out += [(e.name, e.start_ns, e.end_ns) for e in line.events]
    return out


def _inside(events, outer, name):
    """Events called ``name`` that lie within the interval ``outer``."""
    return [e for e in events
            if e[0] == name and outer[1] <= e[1] and e[2] <= outer[2]]


def test_first_call_traces_inside_dispatch_second_does_not(tmp_path,
                                                           shards):
    X, y = shards
    proto = DPQNProtocol(get_problem("logistic"), _cfg())
    keys = jax.random.split(jax.random.PRNGKey(1), REPS)
    jax.profiler.start_trace(str(tmp_path))
    try:
        for call in ("test.first", "test.second"):
            with jax.profiler.TraceAnnotation(call):
                jax.block_until_ready(proto.run_monte_carlo(keys, X, y))
    finally:
        jax.profiler.stop_trace()
    ev = _host_events(tmp_path)
    first = [e for e in ev if e[0] == "test.first"][0]
    second = [e for e in ev if e[0] == "test.second"][0]

    dispatch = _inside(ev, first, spans.PROTOCOL_DISPATCH)
    assert len(dispatch) == 1
    assert len(_inside(ev, dispatch[0], spans.PROTOCOL_TRACE)) == 1
    # the scopes' host spans run at trace time, inside the trace span
    trace = _inside(ev, dispatch[0], spans.PROTOCOL_TRACE)[0]
    for name in spans.ALG1_SCOPES:
        assert _inside(ev, trace, name)

    assert len(_inside(ev, second, spans.PROTOCOL_DISPATCH)) == 1
    assert not _inside(ev, second, spans.PROTOCOL_TRACE)
    assert not any(_inside(ev, second, n) for n in spans.ALG1_SCOPES)
    assert proto.trace_count == 1


def _op_names(hlo_text):
    return re.findall(r'op_name="([^"]*)"', hlo_text)


@pytest.mark.parametrize("center_trust", ["trusted", "untrusted"])
def test_compiled_engine_carries_the_scopes(shards, center_trust):
    X, y = shards
    proto = DPQNProtocol(get_problem("logistic"),
                         _cfg(center_trust=center_trust))
    _, batched = proto._engine("scale")
    keys = jax.random.split(jax.random.PRNGKey(1), REPS)
    byz = jnp.arange(M) < 2
    text = batched.lower(keys, X, y, byz, None, None,
                         -3.0).compile().as_text()
    names = _op_names(text)
    for scope in spans.ALG1_SCOPES:
        # a scope under vmap reads ``vmap(alg1.local)`` in the path
        seg = re.compile(rf"(^|[/(]){re.escape(scope)}([/)]|$)")
        assert any(seg.search(n) for n in names), scope


def test_scopes_change_no_number(shards, monkeypatch):
    """A name scope changes op metadata only: the engine with its scopes
    and the same rounds traced without them agree bit for bit."""
    import contextlib
    from repro.core import protocol
    X, y = shards
    keys = jax.random.split(jax.random.PRNGKey(3), REPS)
    prob = get_problem("logistic")
    scoped = DPQNProtocol(prob, _cfg()).run_monte_carlo(keys, X, y)
    monkeypatch.setattr(protocol, "scope",
                        lambda name: contextlib.nullcontext())
    plain = DPQNProtocol(prob, _cfg()).run_monte_carlo(keys, X, y)
    for a, b in zip(scoped, plain):
        assert jnp.array_equal(a, b)
