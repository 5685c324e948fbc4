"""Share of the roofline of the ingest bodies, in %: the least time the
chip could take for the window's learning steps (``perfbench.work``),
over the device time of the ingest programs in the trace."""
from perfbench import readers


def read(ctx):
    return readers.ingest_roofline_pct(ctx)
