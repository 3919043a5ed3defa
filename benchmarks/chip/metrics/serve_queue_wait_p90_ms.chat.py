"""90th percentile of the time requests waited for a slot
(``ServeReport.queue_waits``)."""
import loadgen


def read(ctx):
    rep = ctx.layer.get("report")
    if rep is None or not len(rep.queue_waits):
        return None
    return loadgen.percentile(rep.queue_waits, 90) * 1e3
