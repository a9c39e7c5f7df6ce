"""dg_flux_ms_per_step (ms): the six face calls' summed event time per
step, on the device that spends most.

Not a roofline share: XLA stages the flux kernel's operands in on-chip
memory (memory space S(1)), so its events leave out the HBM traffic the
algorithm needs, and a share against HBM bandwidth reads over 100%."""

from _common import per_device, traces


def read(ctx):
    ns = [n for n in per_device(ctx, lambda ev, w: traces.kernel_ns(ev, "dg_flux", w)[0]) if n]
    return max(ns) * 1e-6 / ctx.steps if ns else None
