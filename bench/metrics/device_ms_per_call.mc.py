"""Device busy time per ``run_monte_carlo`` call, in ms: the union of
the device's operation intervals in the window over the calls made
(profiler trace)."""


def read(run, red):
    calls = run.values.get("calls")
    if not calls or red.n_devices == 0:
        return None
    return 1e3 * red.busy_s / calls
