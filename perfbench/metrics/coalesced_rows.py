"""Rows per device dispatch of the admission micro-batcher over the
window: the mean of the program's ``figmn_serve_coalesced_rows``."""


def read(ctx):
    n = ctx.get("dispatches")
    return ctx["coalesced_rows_sum"] / n if n else None
