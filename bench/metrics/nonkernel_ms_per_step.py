"""nonkernel_ms_per_step (ms): device busy time per step outside the
``dg_volume`` and ``dg_flux`` events, on the busiest such device."""

from _common import per_device, traces

KERNELS = ("dg_volume", "dg_flux")


def _outside(events, window):
    all_ops = traces.measure(traces.busy(events, window))
    kern = [e for e in events if any(traces.is_kernel(e, k) for k in KERNELS)]
    return all_ops - traces.measure(traces.busy(kern, window))


def read(ctx):
    ns = per_device(ctx, _outside)
    return max(ns) * 1e-6 / ctx.steps if ns else None
