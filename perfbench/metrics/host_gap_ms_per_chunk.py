"""Device idle time between consecutive ingest programs in the trace, in
ms per chunk of the window: the host's share of each chunk."""
from perfbench import readers


def read(ctx):
    return readers.host_gap_ms_per_chunk(ctx)
