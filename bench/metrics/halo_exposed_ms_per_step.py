"""halo_exposed_ms_per_step (ms): per step, the time a collective-permute
is in flight on a device while no other op runs there; the largest device.
None where the trace holds no collective-permute."""

from _common import per_device, traces


def read(ctx):
    has = per_device(ctx, lambda ev, w: bool(traces.collective_in_flight(ev)))
    if not any(has):
        return None
    ns = per_device(ctx, traces.exposed_collective_ns)
    return max(ns) * 1e-6 / ctx.steps
