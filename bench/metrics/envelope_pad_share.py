"""envelope_pad_share (%): rows the envelope gathers per rhs beyond what
the split needs (each block's own and halo rows, bucketed), over the real
elements.  0 on an equal split; a ragged split pads every block to the
largest.  A host count the observed driver takes from the engine's blocks
and the pipeline's envelope signature."""


def read(ctx):
    gathered = ctx.counters.get("gathered_rows_per_rhs")
    needed = ctx.counters.get("block_rows_per_rhs")
    if gathered is None or needed is None:
        return None
    return 100.0 * (gathered - needed) / ctx.elements
