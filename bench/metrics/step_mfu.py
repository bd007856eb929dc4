"""The published network's dense work (`bench.counts.dense_flops_per_image`)
for every image answered in the window, over the trace's device-busy time
times the chips' bf16 peak, in %."""
from bench.readers import step_mfu as read  # noqa: F401
