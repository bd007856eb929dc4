"""A rate-coded network's dense work (`bench.counts_rate.dense_flops_per_image`:
conv0 counted T times) for every image answered in the window, over the
trace's device-busy time times the chips' bf16 peak, in %."""
from bench import counts_rate


def read(ctx):
    t = ctx.trace
    images = len(ctx.window.completed())
    if t is None or t.busy_s <= 0 or not images:
        return None
    work = counts_rate.dense_flops_per_image(ctx.net) * images
    return 100.0 * work / (ctx.chips * t.busy_s * ctx.peak["flops_per_s"])
