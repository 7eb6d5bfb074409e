"""Share of the window's ``run_monte_carlo`` calls that found Algorithm
1's engine built, in %: 100 x (1 - the window's ``repro.protocol.build``
spans over its calls) (program span). ``None`` for a program without
that span."""
try:
    from repro import spans
except ImportError:        # a program from before its spans
    spans = None


def read(run, red):
    build = getattr(spans, "PROTOCOL_BUILD", None)
    calls = run.values.get("calls")
    if build is None or not calls:
        return None
    return 100.0 * (1.0 - red.spans.get(build, (0, 0.0))[0] / calls)
