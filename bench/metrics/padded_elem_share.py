"""padded_elem_share (%): rows the envelope gathers per rhs over the real
elements, less 1 (halo rows plus envelope padding).  A host count the
nested driver takes from the pipeline's envelope signature."""


def read(ctx):
    rows = ctx.counters.get("gathered_rows_per_rhs")
    return 100.0 * (rows / ctx.elements - 1.0) if rows else None
