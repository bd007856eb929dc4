"""Roofline share of the sparse cores (`spike_matmul_mapped`): its device
time against the operations on the spikes each step's results carry and
the bytes of its outputs and weights (`bench.counts.spike_matmul_work`)."""
from bench import counts
from bench.readers import kernel_roofline


def read(ctx):
    return kernel_roofline(
        ctx, "spike_matmul_mapped",
        lambda step: counts.spike_matmul_work(ctx.net, ctx.slots, step.in_spikes))
