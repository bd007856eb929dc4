"""Roofline share of the LIF epilogue (`lif_epilogue`): its device time
against every sparse and FC layer's T membrane updates over every slot,
counted from shapes (`bench.counts.lif_epilogue_work`)."""
from bench import counts
from bench.readers import kernel_roofline


def read(ctx):
    return kernel_roofline(ctx, "lif_epilogue",
                           lambda step: counts.lif_epilogue_work(ctx.net, ctx.slots))
