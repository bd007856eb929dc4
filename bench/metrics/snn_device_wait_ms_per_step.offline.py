"""Host milliseconds per engine step in the program's `snn.device_wait` span: the
host blocked on the chip: `jax.block_until_ready` on the fused graph's
outputs, after its dispatch. From `EngineCore.stats()["host_stages"]` before
and after the window (`bench.stages.ms_per_step`)."""
from bench.stages import ms_per_step


def read(ctx):
    return ms_per_step(ctx, "snn.device_wait")
