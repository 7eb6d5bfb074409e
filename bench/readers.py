"""Shared arithmetic of the per-layer metric readers in ``metrics/``.

A reader is ``read(run, reduction) -> float | None``: ``run`` is the
entry's :class:`bench.run.Run` (its spans, counters and the values the
entry recorded), ``reduction`` the :class:`bench.trace.Reduction` of the
traced window. ``None`` means there was nothing to read, and the
harness leaves the metric out; a share of a roofline or a peak is never
reported as 0 for want of a reading.
"""
from __future__ import annotations

import jax

from bench.peaks import peaks

#: names the compiler gives the order-statistics kernel's device events
#: (the Pallas kernel is ``_ostat_kernel``; XLA names its custom call
#: after the kernel)
OSTAT_MARKERS = ("ostat",)


def is_ostat(name: str) -> bool:
    return any(mark in name for mark in OSTAT_MARKERS)


def device_peaks() -> dict:
    return peaks(jax.devices()[0].device_kind)


def idle_percent(run, red):
    if red.window_s <= 0 or red.n_devices == 0:
        return None
    return 100.0 * red.idle_share


def ostat_roofline(run, red):
    """HBM-bound share of the order-statistics kernel: the bytes its
    calls must move (``run.values["ostat_bytes"]``, from shapes) over the
    chip's HBM bandwidth, divided by the kernel's summed device time."""
    kernel_s = red.op_seconds(is_ostat)
    moved = run.values.get("ostat_bytes")
    if not kernel_s or not moved:
        return None
    return 100.0 * moved / device_peaks()["hbm_bytes_per_s"] / kernel_s
