"""Share of the input layer's (conv0's) spike tiles that the occupancy map
skipped: the mean over the window's steps of ``batch_skip_rate["conv0"]``
(from `SNNRunner`'s results). conv0's tile count is the same at every step,
so no weighting is needed. Only a rate-coded net maps conv0; a direct-coded
one has nothing here to read."""


def read(ctx):
    rates = [step.skip["conv0"] for step in ctx.window.steps
             if "conv0" in step.skip]
    return sum(rates) / len(rates) if rates else None
