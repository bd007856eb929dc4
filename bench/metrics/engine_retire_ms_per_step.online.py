"""Host milliseconds per engine step in the program's `engine.retire` span: the
engine's result path after the runner returns: numerics screen, partials,
scheduler and obs hooks, routing. From `EngineCore.stats()["host_stages"]`
before and after the window (`bench.stages.ms_per_step`)."""
from bench.stages import ms_per_step


def read(ctx):
    return ms_per_step(ctx, "engine.retire")
