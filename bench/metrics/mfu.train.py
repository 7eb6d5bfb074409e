"""Model FLOP/s utilisation of the quasi-Newton step: the forward and
backward passes Algorithm 1 requires (3 per machine per step) at the
published shapes, over the window's steps, over the window's time and
the chip's bf16 peak."""
from bench.readers import device_peaks


def read(run, red):
    flops = run.values.get("window_model_flops")
    if not flops:
        return None
    return 100.0 * flops / run.window_s / device_peaks()["bf16_flops_per_s"]
