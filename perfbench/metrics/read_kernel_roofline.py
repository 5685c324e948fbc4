"""Share of the roofline of the read kernels, in %: the least time the
chip could take for the window's answered rows and dispatches
(``perfbench.work``), over the device time of the read programs."""
from perfbench import readers


def read(ctx):
    return readers.read_roofline_pct(ctx)
