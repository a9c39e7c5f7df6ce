"""observe_idle_ms_per_chunk (ms): device idle time inside the window on
the idlest device, over the observe chunks the window ran: what each
chunk's sync, re-solve, table rebuild and next enqueue leave the chip
waiting for."""

from _common import per_device, traces


def read(ctx):
    chunks = ctx.counters.get("observe_chunks")
    idle = per_device(ctx, lambda ev, w: traces.measure(traces.idle_gaps(ev, w)))
    return max(idle) * 1e-6 / chunks if idle and chunks else None
