"""Host milliseconds per engine step in the program's `snn.input` span: the stack
of the slot batch's images on the device: a `jnp.asarray` per request, then
`jnp.stack`. From `EngineCore.stats()["host_stages"]` before and after the
window (`bench.stages.ms_per_step`)."""
from bench.stages import ms_per_step


def read(ctx):
    return ms_per_step(ctx, "snn.input")
