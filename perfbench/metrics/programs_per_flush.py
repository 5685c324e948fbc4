"""Device programs per admission flush: every program execution in the
window's trace over the window's ``serve.flush`` spans.  One is the read
itself; the rest are the batch's concatenation and the per-answer
slices."""
from perfbench import spans


def read(ctx):
    t = ctx.get("trace")
    if not t or not t["programs"]:
        return None
    n = spans.flushes(t["trace"], t["win"])
    return sum(k for _, k in t["programs"].values()) / n if n else None
