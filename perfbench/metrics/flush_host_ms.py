"""Host time of one admission flush, in ms: the mean over the window's
``serve.flush`` spans of each span less its ``serve.score`` or
``serve.predict`` child (the device read); the rest is gathering the
batch and handing the answers back, with the device idle."""
from perfbench import spans


def read(ctx):
    t = ctx.get("trace")
    return spans.flush_host_ms(t["trace"], t["win"]) if t else None
