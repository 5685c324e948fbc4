"""Admission-queue wait of a read, in ms: the mean of the program's
``figmn_serve_queue_wait_seconds`` (submit to the pop of the request's
batch), observed once per queued request.  None where the histogram is
missing, or counted fewer requests than the window answered."""

NAME = "figmn_serve_queue_wait_seconds"


def read(ctx):
    from repro.obs import registry
    hists = [m for m in registry.default_registry().collect()
             if m.name == NAME]
    count = sum(h.count for h in hists)
    if not hists or not count or count < ctx.get("rows", 0):
        return None
    return 1e3 * sum(h.sum for h in hists) / count
