"""Roofline share of the sparse cores (`spike_matmul_mapped`) of a
rate-coded net, whose launches include the input layer's: their device time
against the operations on every layer's input events, conv0's included,
and the bytes of their outputs and weights (`bench.counts_rate`)."""
from bench import counts_rate
from bench.readers import kernel_roofline


def read(ctx):
    if not all("conv0" in step.in_spikes for step in ctx.window.steps):
        return None         # conv0 is not a sparse layer here
    return kernel_roofline(
        ctx, "spike_matmul_mapped",
        lambda step: counts_rate.spike_matmul_work(ctx.net, ctx.slots,
                                                   step.in_spikes))
