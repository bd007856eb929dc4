"""Host milliseconds per engine step in the program's `snn.split` span: the per-
request split of each mapped layer's row occupancy (skip rates, per-timestep
occupancy). From `EngineCore.stats()["host_stages"]` before and after the
window (`bench.stages.ms_per_step`)."""
from bench.stages import ms_per_step


def read(ctx):
    return ms_per_step(ctx, "snn.split")
