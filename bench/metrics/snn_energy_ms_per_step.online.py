"""Host milliseconds per engine step in the program's `snn.energy` span: the
paper's Eq. 3 energy estimates, one per slot and one for the batch. From
`EngineCore.stats()["host_stages"]` before and after the window
(`bench.stages.ms_per_step`)."""
from bench.stages import ms_per_step


def read(ctx):
    return ms_per_step(ctx, "snn.energy")
