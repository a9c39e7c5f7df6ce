"""dg_volume_roofline (%): the volume kernel's share of its roofline.
Work charged: ``work.kernel_work("dg_volume")`` over the real elements."""

from _common import roofline_share


def read(ctx):
    return roofline_share(ctx, "dg_volume")
