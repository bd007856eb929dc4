"""95th percentile, over the answered requests, of the time from a
request's due time to the start of the engine step that admitted it (an
SNN request is admitted and answered in one step)."""
from bench import stats


def read(ctx):
    start = {s.index: s.start for s in ctx.window.steps}
    waits = [1000.0 * (start[r.step] - r.due) for r in ctx.window.requests
             if r.status == "ok" and r.step in start]
    return stats.percentile(waits, 95) if waits else None
