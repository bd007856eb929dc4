"""Host milliseconds per engine step in the program's `snn.fetch` span: every
device-to-host read of the logits and the per-layer stats. From
`EngineCore.stats()["host_stages"]` before and after the window
(`bench.stages.ms_per_step`)."""
from bench.stages import ms_per_step


def read(ctx):
    return ms_per_step(ctx, "snn.fetch")
