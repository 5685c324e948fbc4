"""Device idle share of the traced window, in %: one minus the union of
the device's operation intervals over the window's length."""
from perfbench import readers


def read(ctx):
    return readers.idle_pct(ctx)
