"""The ``engine_reuse.mc`` reader on a made-up reduction of a window."""
import types

import pytest

from bench import manifest
from bench.run import load_module
from repro import spans


@pytest.fixture
def reader():
    return load_module(manifest.metric_path("engine_reuse.mc"),
                       "bench_metric_engine_reuse_mc")


def _red(builds):
    got = {spans.PROTOCOL_DISPATCH: (4, 0.02)}
    if builds:
        got[spans.PROTOCOL_BUILD] = (builds, 1e-4 * builds)
    return types.SimpleNamespace(spans=got)


def _run(calls):
    return types.SimpleNamespace(values={"calls": calls})


@pytest.mark.parametrize("builds,share", [(0, 100.0), (1, 75.0), (4, 0.0)])
def test_share_of_calls_without_a_build(reader, builds, share):
    assert reader.read(_run(4), _red(builds)) == pytest.approx(share)


@pytest.mark.parametrize("program_spans", [
    None, types.SimpleNamespace(PROTOCOL_DISPATCH=spans.PROTOCOL_DISPATCH)])
def test_a_program_without_the_build_span_reads_none(reader, monkeypatch,
                                                    program_spans):
    monkeypatch.setattr(reader, "spans", program_spans)
    assert reader.read(_run(4), _red(0)) is None


def test_a_window_without_calls_reads_none(reader):
    assert reader.read(_run(0), _red(0)) is None
