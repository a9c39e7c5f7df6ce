"""What the per-layer readers share: per-device reductions of the trace."""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import traces  # noqa: E402
import work  # noqa: E402


def per_device(ctx, fn):
    """``fn(events, window)`` for each traced device, or [] without a trace."""
    if ctx.trace is None or not ctx.trace.devices:
        return []
    window = ctx.trace.window
    return [fn(events, window) for events in ctx.trace.devices.values()]


def roofline_share(ctx, kernel: str):
    """100 x the least time the chip could take for the kernel's work over
    the window, over the kernel's summed event time, all devices together.
    None where the trace holds no event of the kernel."""
    sums = per_device(ctx, lambda ev, w: traces.kernel_ns(ev, kernel, w)[0])
    kernel_s = sum(sums) * 1e-9
    if kernel_s <= 0:
        return None
    least, _ = work.least_seconds(kernel, ctx.order, ctx.elements, ctx.rhs_evals,
                                  ctx.device_kind)
    return 100.0 * least / kernel_s
