"""device_idle_share (%): 1 less the union of busy intervals over the
traced window, on the idlest device."""

from _common import per_device, traces


def read(ctx):
    busy = per_device(ctx, lambda ev, w: traces.measure(traces.busy(ev, w)) / (w[1] - w[0]))
    return 100.0 * (1.0 - min(busy)) if busy else None
