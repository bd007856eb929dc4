"""Share of the six sparse conv layers' spike tiles that the occupancy map
skipped, over the window's steps: each step's ``batch_skip_rate`` per
layer (from `SNNRunner`'s results), weighted by the layer's tile count."""


def read(ctx):
    skipped = total = 0.0
    for step in ctx.window.steps:
        for layer, tiles in ctx.tiles.items():
            if layer in step.skip:
                skipped += step.skip[layer] * tiles
                total += tiles
    return skipped / total if total else None
