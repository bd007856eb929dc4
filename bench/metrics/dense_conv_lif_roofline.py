"""Roofline share of the dense core (`dense_conv_lif`): its device time
against the input layer's conv and T LIF steps over every slot, counted
from shapes (`bench.counts.dense_conv_lif_work`)."""
from bench import counts
from bench.readers import kernel_roofline


def read(ctx):
    return kernel_roofline(ctx, "dense_conv_lif",
                           lambda step: counts.dense_conv_lif_work(ctx.net, ctx.slots))
