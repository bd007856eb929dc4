"""Occupied slot-steps over slots x steps in the window, from the deltas of
`EngineCore.stats()` taken before and after it."""


def read(ctx):
    before, after = ctx.engine
    steps = after["steps_run"] - before["steps_run"]
    if steps <= 0:
        return None
    occupied = (after["slot_occupancy"] * after["steps_run"]
                - before["slot_occupancy"] * before["steps_run"])
    return occupied / steps
